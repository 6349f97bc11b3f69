"""Tunable tiled matmul Pallas kernel — the search-space substrate.

This template IS the object the agentic optimizer tunes: a candidate
kernel is a config {bm, bn, bk, epilogue, transpose flags, ...} of this
pallas_call.  TPU adaptation of the paper's CUDA candidates: tiling is
expressed as BlockSpecs over (M, N, K) with the K loop as the innermost
grid dimension accumulating into the VMEM output block; the MXU wants
the last two dims in multiples of (8, 128) for f32 / (16, 128) for bf16.

Supported task surface (KernelBench T2-T18 analogues):
  * plain C = A @ B, with optional A^T / B^T layouts (T8-T10),
  * masked variants: upper/lower-triangular output (T6, T7),
  * fused epilogues: relu / leaky_relu / gelu / sigmoid / scale / none
    (T11-T18 Gemm+Act fusions).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _epilogue(x, kind: str, scale: float):
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "leaky_relu":
        return jnp.where(x > 0, x, 0.01 * x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "sigmoid":
        return jax.nn.sigmoid(x)
    if kind == "scale":
        return x * scale
    return x


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int, epilogue: str,
               scale: float, mask: Optional[str], bm: int, bn: int):
    """Grid = (M/bm, N/bn, K/bk); K is the innermost (fastest) axis."""
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        acc = _epilogue(acc_ref[...], epilogue, scale)
        if mask is not None:
            rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
            cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
            keep = rows >= cols if mask == "lower" else rows <= cols
            acc = jnp.where(keep, acc, 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128, bn: int = 128,
           bk: int = 128, epilogue: str = "none", scale: float = 1.0,
           mask: Optional[str] = None, interpret: Optional[bool] = None,
           out_dtype=None) -> jnp.ndarray:
    """C[M,N] = epilogue(A[M,K] @ B[K,N]) with optional triangular mask."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"shape {(M, K, N)} not divisible by blocks {(bm, bn, bk)}"
    nk = K // bk
    out_dtype = out_dtype or a.dtype
    kern = functools.partial(_mm_kernel, nk=nk, epilogue=epilogue,
                             scale=scale, mask=mask, bm=bm, bn=bn)
    return pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a, b)
