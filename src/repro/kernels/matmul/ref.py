"""Pure-jnp oracle for the tiled matmul template."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def matmul_ref(a, b, *, epilogue: str = "none", scale: float = 1.0,
               mask: Optional[str] = None, out_dtype=None):
    c = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    if epilogue == "relu":
        c = jnp.maximum(c, 0.0)
    elif epilogue == "leaky_relu":
        c = jnp.where(c > 0, c, 0.01 * c)
    elif epilogue == "gelu":
        c = jax.nn.gelu(c, approximate=True)
    elif epilogue == "sigmoid":
        c = jax.nn.sigmoid(c)
    elif epilogue == "scale":
        c = c * scale
    if mask == "lower":
        c = jnp.tril(c)
    elif mask == "upper":
        c = jnp.triu(c)
    return c.astype(out_dtype or a.dtype)
