"""Mesh and shard_map helpers over the installed jax (0.9).

Meshes are built with explicit ``Auto`` axis types, the sharding
semantics the rules in ``distributed/sharding.py`` assume.  Everything
that builds a mesh — launch/mesh.py, tests — goes through these helpers
instead of calling jax directly.
"""
from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def device_mesh_shape(model: int = 1) -> int:
    """Largest 'data' extent the visible devices support for a
    ``(data, model)`` mesh.  CPU runners fan out via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    the first jax device query); with a plain single-device backend
    this is simply 1."""
    n = jax.device_count()
    return max(n // max(model, 1), 1)


def make_abstract_mesh(axis_shapes: Sequence[int],
                       axis_names: Sequence[str]):
    """``AbstractMesh`` with Auto axis types."""
    return jax.sharding.AbstractMesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
