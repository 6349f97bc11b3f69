"""Causal flash attention (prefill) as a Pallas TPU kernel.

TPU adaptation of the FlashAttention recurrence: the Q-block lives in
VMEM across the whole KV sweep; K/V are consumed in ``bkv``-sized
chunks with the online-softmax running (max, denom) carried in VREGs.
Grid = (batch*kv_heads, S/bq); GQA is handled by processing all G query
heads of a KV head together (they share the K/V traffic — the same
reuse argument as FlashAttention-2's head packing).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bkv: int, seq: int,
               scale: float, causal: bool):
    """q_ref (1, G, bq, Dh); k_ref/v_ref (1, seq, Dh)."""
    qi = pl.program_id(1)
    _, G, _, Dh = q_ref.shape
    q = q_ref[0].astype(jnp.float32) * scale            # (G, bq, Dh)

    q_lo = qi * bq
    # causal: only sweep KV chunks that intersect the triangle
    nkv = (seq // bkv) if not causal else (q_lo + bq + bkv - 1) // bkv

    def body(ci, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, pl.ds(ci * bkv, bkv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ci * bkv, bkv), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (G, bq, bkv)
        if causal:
            rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            cols = ci * bkv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 1)
            s = jnp.where((cols <= rows)[None], s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))    # (G, bq)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (G, bq, Dh)
        acc = acc * alpha[..., None] + pv
        return acc, m_new, l_new

    acc0 = jnp.zeros((G, bq, Dh), jnp.float32)
    m0 = jnp.full((G, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, bq), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, nkv, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l_i, 1e-20)[..., None]
                ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    bq: int = 128, bkv: int = 128, causal: bool = True,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B, S, H, Dh); k/v (B, S, KV, Dh) -> (B, S, H, Dh)."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    assert S % bq == 0 and S % bkv == 0, (S, bq, bkv)
    scale = 1.0 / math.sqrt(Dh)
    # (B, KV, G, S, Dh) so one grid step owns one KV head's query group
    qg = q.reshape(B, S, KV, G, Dh).transpose(0, 2, 3, 1, 4)
    kk = k.transpose(0, 2, 1, 3)                         # (B, KV, S, Dh)
    vv = v.transpose(0, 2, 1, 3)
    qg = qg.reshape(B * KV, G, S, Dh)
    kk = kk.reshape(B * KV, S, Dh)
    vv = vv.reshape(B * KV, S, Dh)
    kern = functools.partial(_fa_kernel, bq=bq, bkv=bkv, seq=S, scale=scale,
                             causal=causal)
    out = pl.pallas_call(
        kern,
        grid=(B * KV, S // bq),
        in_specs=[
            pl.BlockSpec((1, G, bq, Dh), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, S, Dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, Dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, Dh), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, S, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qg, kk, vv)
    out = out.reshape(B, KV, G, S, Dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, Dh)
