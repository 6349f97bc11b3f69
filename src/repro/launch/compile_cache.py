"""Where JAX's persistent compilation cache lives.

A cold run compiles the decode step and one suffix-prefill executable
per (rows, pow2 length) bucket; the persistent cache lets the next
process load them instead.  The cache directory is part of what a hit
needs, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment names one (JAX reads that variable itself), otherwise
``.jax_cache`` at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory
    and return that directory.  Call once, before the first compile."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
