"""Pallas kernels vs pure-jnp oracles (interpret mode) + config sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline CI: no PyPI access
    from _hypothesis_stub import given, settings, strategies as st

from repro.kernels.matmul.kernel import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.matmul.ops import estimate_cost, reference_cost
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.kernel import (decode_attention,
                                                   decode_attention_paged)
from repro.kernels.decode_attention.ops import decode_attention_paged_op
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                decode_attention_paged_ref)
from repro.kernels.ssd.kernel import ssd_scan
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.rglru.kernel import rglru_scan
from repro.kernels.rglru.ref import rglru_ref

RS = np.random.RandomState(0)


# ------------------------------------------------------------------ matmul
@pytest.mark.parametrize("epilogue", ["none", "relu", "gelu", "sigmoid",
                                      "leaky_relu", "scale"])
@pytest.mark.parametrize("mask", [None, "lower", "upper"])
def test_matmul_epilogues(epilogue, mask):
    a = jnp.asarray(RS.randn(128, 64), jnp.float32)
    b = jnp.asarray(RS.randn(64, 128), jnp.float32)
    out = matmul(a, b, bm=64, bn=128, bk=32, epilogue=epilogue,
                 scale=0.5, mask=mask)
    ref = matmul_ref(a, b, epilogue=epilogue, scale=0.5, mask=mask)
    np.testing.assert_allclose(out, ref, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(
    mi=st.sampled_from([1, 2, 4]),
    ni=st.sampled_from([1, 2]),
    ki=st.sampled_from([1, 2, 4]),
    bm=st.sampled_from([32, 64]),
    bn=st.sampled_from([64, 128]),
    bk=st.sampled_from([32, 64]),
    dt=st.sampled_from(["float32", "bfloat16"]),
)
def test_matmul_shape_dtype_sweep(mi, ni, ki, bm, bn, bk, dt):
    """Property: the kernel matches the oracle for every (shape, block,
    dtype) combination — the invariant the agentic search relies on."""
    M, N, K = mi * bm, ni * bn, ki * bk
    rs = np.random.RandomState(M * 7 + N * 3 + K)
    a = jnp.asarray(rs.randn(M, K), dt)
    b = jnp.asarray(rs.randn(K, N), dt)
    out = matmul(a, b, bm=bm, bn=bn, bk=bk)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=1e-3 if dt == "float32" else 5e-2,
        rtol=1e-3 if dt == "float32" else 5e-2)


def test_matmul_cost_model_monotonic():
    """Bigger tiles => less HBM traffic (more reuse); runtime reflects
    the roofline max(compute, memory)."""
    small = estimate_cost(1024, 1024, 1024, bm=8, bn=128, bk=128)
    big = estimate_cost(1024, 1024, 1024, bm=256, bn=256, bk=128)
    assert big.hbm_bytes < small.hbm_bytes
    assert big.runtime_s <= small.runtime_s
    ref = reference_cost(1024, 1024, 1024)
    assert ref.runtime_s >= big.runtime_s


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,S,H,KV,Dh,bq,bkv", [
    (2, 256, 8, 2, 64, 128, 64),
    (1, 128, 4, 4, 32, 64, 128),
    (2, 128, 6, 1, 16, 32, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, S, H, KV, Dh, bq, bkv, causal):
    q = jnp.asarray(RS.randn(B, S, H, Dh), jnp.float32)
    k = jnp.asarray(RS.randn(B, S, KV, Dh), jnp.float32)
    v = jnp.asarray(RS.randn(B, S, KV, Dh), jnp.float32)
    out = flash_attention(q, k, v, bq=bq, bkv=bkv, causal=causal)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5)


# -------------------------------------------------------- decode attention
@pytest.mark.parametrize("B,H,KV,Dh,S,clen", [
    (2, 8, 2, 64, 256, 100),
    (1, 4, 1, 32, 128, 128),
    (2, 6, 3, 16, 256, 17),
    (1, 8, 8, 16, 128, 1),
])
def test_decode_attention(B, H, KV, Dh, S, clen):
    q = jnp.asarray(RS.randn(B, H, Dh), jnp.float32)
    k = jnp.asarray(RS.randn(B, S, KV, Dh), jnp.float32)
    v = jnp.asarray(RS.randn(B, S, KV, Dh), jnp.float32)
    out = decode_attention(q, k, v, clen, bkv=64)
    ref = decode_attention_ref(q, k, v, clen)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_decode_attention_per_row_lengths():
    """Continuous batching: every row at its own depth."""
    B, H, KV, Dh, S = 3, 6, 3, 16, 256
    q = jnp.asarray(RS.randn(B, H, Dh), jnp.float32)
    k = jnp.asarray(RS.randn(B, S, KV, Dh), jnp.float32)
    v = jnp.asarray(RS.randn(B, S, KV, Dh), jnp.float32)
    lens = jnp.asarray([5, 200, 64], jnp.int32)
    out = decode_attention(q, k, v, lens, bkv=64)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("B,H,KV,Dh,P,ps,nb", [
    (2, 8, 2, 32, 16, 64, 3),
    (1, 4, 4, 16, 8, 128, 2),
    (3, 6, 1, 16, 32, 64, 4),
])
def test_decode_attention_paged_block_table(B, H, KV, Dh, P, ps, nb):
    """Block-table kernel (scalar-prefetched table drives the DMA grid)
    and the gather-in-wrapper fallback both match the paged oracle on
    scattered, row-distinct page placements."""
    q = jnp.asarray(RS.randn(B, H, Dh), jnp.float32)
    kp = jnp.asarray(RS.randn(P, ps, KV, Dh), jnp.float32)
    vp = jnp.asarray(RS.randn(P, ps, KV, Dh), jnp.float32)
    bt = jnp.asarray(RS.choice(P, size=B * nb, replace=False
                               ).reshape(B, nb), jnp.int32)
    lens = jnp.asarray(RS.randint(1, nb * ps + 1, size=B), jnp.int32)
    ref = decode_attention_paged_ref(q, kp, vp, bt, lens)
    out = decode_attention_paged(q, kp, vp, bt, lens)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    gathered = decode_attention_paged_op(q, kp, vp, bt, lens,
                                         use_pallas=True, gather=True)
    np.testing.assert_allclose(gathered, ref, atol=2e-5)


# --------------------------------------------------------------------- ssd
@pytest.mark.parametrize("B,S,HS,P,N,chunk", [
    (2, 128, 4, 16, 8, 32),
    (1, 64, 2, 8, 16, 64),
    (1, 96, 3, 8, 8, 32),
])
def test_ssd_scan(B, S, HS, P, N, chunk):
    x = jnp.asarray(RS.randn(B, S, HS, P) * 0.5, jnp.float32)
    b = jnp.asarray(RS.randn(B, S, N) * 0.5, jnp.float32)
    c = jnp.asarray(RS.randn(B, S, N) * 0.5, jnp.float32)
    dt = jnp.asarray(RS.rand(B, S, HS) * 0.2, jnp.float32)
    a = jnp.asarray(-np.exp(RS.rand(HS)), jnp.float32)
    y, h = ssd_scan(x, b, c, dt, a, chunk=chunk)
    yr, hr = ssd_ref(x, b, c, dt, a)
    np.testing.assert_allclose(y, yr, atol=1e-4)
    np.testing.assert_allclose(h, hr, atol=1e-4)


# ------------------------------------------------------------------- rglru
@settings(max_examples=8, deadline=None)
@given(B=st.sampled_from([1, 2]), S=st.sampled_from([128, 256]),
       R=st.sampled_from([32, 64]), block=st.sampled_from([64, 128]))
def test_rglru_scan(B, S, R, block):
    rs = np.random.RandomState(B * 100 + S + R)
    a = jnp.asarray(0.8 + 0.19 * rs.rand(B, S, R), jnp.float32)
    b = jnp.asarray(rs.randn(B, S, R) * 0.3, jnp.float32)
    out = rglru_scan(a, b, block=block)
    ref = rglru_ref(a, b)
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_rglru_strong_decay_underflow_guard():
    a = jnp.full((1, 256, 32), 0.01, jnp.float32)   # brutal decay
    b = jnp.asarray(RS.randn(1, 256, 32), jnp.float32)
    out = rglru_scan(a, b, block=128)
    ref = rglru_ref(a, b)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, ref, atol=1e-3)


def test_interpret_mode_follows_platform(monkeypatch):
    from repro.kernels import resolve_interpret
    assert resolve_interpret() is (jax.default_backend() == "cpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        resolve_interpret()
