"""Mamba-2 SSD (state-space dual) chunked scan as a Pallas TPU kernel.

Grid = (B*HS-groups?, nc) with the chunk axis innermost: the recurrent
state h (N, P per head-group block) lives in VMEM scratch and persists
across the sequential chunk steps — TPU grids iterate in order, so the
inter-chunk recurrence costs no HBM round-trips.  Intra-chunk work
(the L-masked C·Bᵀ attention dual) is MXU matmuls on (Q, N)/(Q, P)
tiles.  This is the TPU-native replacement for the paper-adjacent CUDA
SSD kernels (hardware adaptation per DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, hout_ref,
                h_ref, *, nc: int, Q: int):
    """Blocks per (batch*head, chunk):
       x_ref (1, Q, P); b_ref/c_ref (1, Q, N); dt_ref (1, Q, 1);
       a_ref (1, 1) SMEM-like scalar decay rate A (negative);
       scratch h_ref (N, P); outputs y (1, Q, P), hout (1, N, P)."""
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)                    # (Q, P)
    Bm = b_ref[0].astype(jnp.float32)                   # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)                   # (Q, N)
    dt = dt_ref[0].astype(jnp.float32)                  # (Q, 1)
    A = a_ref[0, 0]                                     # scalar < 0

    s = dt[:, 0] * A                                    # (Q,) log-decay
    cum = jnp.cumsum(s)                                 # (Q,)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    d = cum[:, None] - cum[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(lj <= li, jnp.exp(d), 0.0)            # (Q, Q)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    xdt = x * dt                                        # (Q, P)
    y_intra = jnp.dot(scores * L, xdt,
                      preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                      # (N, P)
    y_inter = jnp.exp(cum)[:, None] * jnp.dot(
        Cm, h, preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update: h <- e^{sum s} h + sum_j e^{cum_Q - cum_j} B_j (x_j dt_j)
    decay_to_end = jnp.exp(cum[-1] - cum)               # (Q,)
    h_new = jnp.exp(cum[-1]) * h + jnp.dot(
        (Bm * decay_to_end[:, None]).T, xdt,
        preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    @pl.when(ci == nc - 1)
    def _store():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


def ssd_scan(x: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
             dt: jnp.ndarray, a: jnp.ndarray, *, chunk: int = 64,
             interpret: Optional[bool] = None):
    """x (B,S,HS,P); b/c (B,S,N); dt (B,S,HS); a (HS,) negative decays.
    Returns y (B,S,HS,P), h_final (B,HS,N,P)."""
    B, S, HS, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    # lay out as (B*HS, S, ·) so one grid row owns one head's scan
    xs = x.transpose(0, 2, 1, 3).reshape(B * HS, S, P)
    bs = jnp.broadcast_to(b[:, None], (B, HS, S, N)).reshape(B * HS, S, N)
    cs = jnp.broadcast_to(c[:, None], (B, HS, S, N)).reshape(B * HS, S, N)
    dts = dt.transpose(0, 2, 1).reshape(B * HS, S, 1)
    aa = jnp.broadcast_to(a[None], (B, HS)).reshape(B * HS, 1)
    kern = functools.partial(_ssd_kernel, nc=nc, Q=chunk)
    y, hout = pl.pallas_call(
        kern,
        grid=(B * HS, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, 1), lambda g, ci: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, N, P), lambda g, ci: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * HS, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * HS, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xs, bs, cs, dts, aa)
    y = y.reshape(B, HS, S, P).transpose(0, 2, 1, 3)
    return y, hout.reshape(B, HS, N, P)
