"""Jitted wrapper: flash attention with jnp fallback."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "causal",
                                             "use_pallas", "interpret"))
def attention_op(q, k, v, *, bq=128, bkv=128, causal=True,
                 use_pallas=True, interpret=None):
    if use_pallas:
        return flash_attention(q, k, v, bq=bq, bkv=bkv, causal=causal,
                               interpret=interpret)
    return attention_ref(q, k, v, causal=causal)
