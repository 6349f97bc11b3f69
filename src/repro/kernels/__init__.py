"""Pallas kernels of the served and eval paths.

Every kernel takes ``interpret=None`` by default and resolves it here,
from the platform JAX computes on: the Pallas interpreter on the CPU
(tests), the Mosaic compiler on a TPU.  There is no third lowering, so
any other platform is an error rather than a silent interpreter run.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` as given, or chosen by ``jax.default_backend()``
    when it is None: True on ``cpu``, False on ``tpu``."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas lowering for platform {platform!r}: kernels run "
        f"interpreted on 'cpu' or compiled on 'tpu'")
