"""Jitted wrapper: SSD scan with jnp fallback."""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd.kernel import ssd_scan
from repro.kernels.ssd.ref import ssd_ref


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd_op(x, b, c, dt, a, *, chunk=64, use_pallas=True, interpret=None):
    if use_pallas:
        return ssd_scan(x, b, c, dt, a, chunk=chunk, interpret=interpret)
    return ssd_ref(x, b, c, dt, a)
