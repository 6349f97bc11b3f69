"""Random weights for a dense GQA decoder, made on the device from the
seed in one jitted call, in the type they are served in.

The tree has the layout the program's served path takes as ``params``
(``embed.tokens``, ``layers[i].{ln1,attn,ln2,mlp}``, ``final_norm``);
``run.py`` checks it against the program's own abstract parameters
before use.  Norm scales and biases are drawn too, not left at one and
zero, so that the comparison with the reference sees whether the
program applies them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def jax_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) % (2 ** 32))


def _leaf_specs(cfg: dict):
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    # (path, shape, kind, fan_in); kind: w (bf16 normal / sqrt(fan_in)),
    # b (bf16 bias), s (f32 scale near 1), e (bf16 embedding)
    specs = [(("embed", "tokens"), (v, d), "e", d)]
    for i in range(cfg["num_hidden_layers"]):
        L = ("layers", i)
        specs += [
            (L + ("ln1", "scale"), (d,), "s", 0),
            (L + ("attn", "wq"), (d, h, dh), "w", d),
            (L + ("attn", "wk"), (d, kv, dh), "w", d),
            (L + ("attn", "wv"), (d, kv, dh), "w", d),
            (L + ("attn", "wo"), (h, dh, d), "w", h * dh),
        ]
        if cfg.get("attention_bias", False):
            specs += [(L + ("attn", "bq"), (h, dh), "b", 0),
                      (L + ("attn", "bk"), (kv, dh), "b", 0),
                      (L + ("attn", "bv"), (kv, dh), "b", 0)]
        if cfg.get("qk_norm", False):
            specs += [(L + ("attn", "q_norm"), (dh,), "s", 0),
                      (L + ("attn", "k_norm"), (dh,), "s", 0)]
        specs += [
            (L + ("ln2", "scale"), (d,), "s", 0),
            (L + ("mlp", "wi"), (d, f), "w", d),
            (L + ("mlp", "wg"), (d, f), "w", d),
            (L + ("mlp", "wo"), (f, d), "w", f),
        ]
    specs.append((("final_norm", "scale"), (d,), "s", 0))
    return specs


def _leaf(key, shape, kind, fan_in):
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
    specs = _leaf_specs(cfg)
    key_cfg = tuple((p, s, k, f) for p, s, k, f in specs)
    leaves = _generate(key_cfg, jax_key(seed))
    tree: dict = {}
    for (path, _s, _k, _f), val in zip(specs, leaves):
        _insert(tree, path, val)
    return tree


@functools.partial(jax.jit, static_argnums=(0,))
def _generate(specs, key):
    return [_leaf(jax.random.fold_in(key, i), s, k, f)
            for i, (_p, s, k, f) in enumerate(specs)]
