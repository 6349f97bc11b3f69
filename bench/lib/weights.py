"""Random weights, made on the device from the seed in one jitted call,
in the type they are served in.

The tree is the one the configuration's arch module lays out
(``leaf_specs`` in ``bench/arch/<arch>.py``): the layout the program's
served path takes as ``params``, which ``run.py`` checks against the
program's own abstract parameters before use.  Leaf ``i`` is drawn from
``fold_in(key, i)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.lib import arch

KINDS = ("w", "e", "b", "s")


def jax_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) % (2 ** 32))


def _leaf(key, shape, kind, fan_in, extra):
    if kind not in KINDS:
        return extra[kind](key, shape, fan_in)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        return (z / math.sqrt(fan_in)).astype(jnp.bfloat16)
    if kind == "e":
        return (z * 0.02).astype(jnp.bfloat16)
    if kind == "b":
        return (z * 0.1).astype(jnp.bfloat16)
    return 1.0 + 0.1 * z                          # f32 norm scale


def _insert(tree: dict, path, value) -> None:
    node = tree
    for k in path[:-1]:
        if k == "layers":
            node = node.setdefault("layers", [])
            continue
        if isinstance(node, list):
            while len(node) <= k:
                node.append({})
            node = node[k]
        else:
            node = node.setdefault(k, {})
    node[path[-1]] = value


def make_params(cfg: dict, seed: int):
    """The whole weight tree, on the default device, in one call."""
    mod = arch.module(cfg)
    specs = tuple(mod.leaf_specs(cfg))
    extra = tuple(sorted(getattr(mod, "LEAF_KINDS", {}).items()))
    leaves = _generate(specs, extra, jax_key(seed))
    tree: dict = {}
    for (path, _s, _k, _f), val in zip(specs, leaves):
        _insert(tree, path, val)
    return tree


@functools.partial(jax.jit, static_argnums=(0, 1))
def _generate(specs, extra, key):
    extra = dict(extra)
    return [_leaf(jax.random.fold_in(key, i), s, k, f, extra)
            for i, (_p, s, k, f) in enumerate(specs)]
