"""Jitted wrapper: RG-LRU scan with jnp fallback."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rglru.kernel import rglru_scan
from repro.kernels.rglru.ref import rglru_ref


@functools.partial(jax.jit, static_argnames=("block", "use_pallas",
                                             "interpret"))
def rglru_op(a, b, *, block=128, use_pallas=True, interpret=None):
    if use_pallas:
        return rglru_scan(a, b, block=block, interpret=interpret)
    return rglru_ref(a, b)
