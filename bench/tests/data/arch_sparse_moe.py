"""A second architecture for the harness's tests: a decoder whose every
layer routes over sparse SwiGLU experts (the program's ``moe`` layer,
as in Phi-3.5-MoE), written against ``bench/arch/dense_gqa.py``'s
contract.  Its router is float32, a leaf kind of its own.

The configuration file gives ``num_local_experts`` experts of width
``intermediate_size``, ``num_experts_per_tok`` of them a token.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp


def _router(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


LEAF_KINDS = {"r": _router}


def _sizes(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["num_local_experts"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


def widths(cfg: dict) -> dict:
    d, h, kv, dh, f, e, n_layers, vocab = _sizes(cfg)
    return {"num_layers": n_layers, "d_model": d, "num_heads": h,
            "num_kv_heads": kv, "head_dim": dh, "moe_d_ff": f,
            "num_experts": e,
            "experts_per_token": cfg["num_experts_per_tok"],
            "vocab_size": vocab,
            "tie_embeddings": bool(cfg["tie_word_embeddings"])}


def leaf_specs(cfg: dict):
    d, h, kv, dh, f, e, n_layers, v = _sizes(cfg)
    specs = [(("embed", "tokens"), (v, d), "e", d)]
    for i in range(n_layers):
        L = ("layers", i)
        specs += [
            (L + ("ln1", "scale"), (d,), "s", 0),
            (L + ("attn", "wq"), (d, h, dh), "w", d),
            (L + ("attn", "wk"), (d, kv, dh), "w", d),
            (L + ("attn", "wv"), (d, kv, dh), "w", d),
            (L + ("attn", "wo"), (h, dh, d), "w", h * dh),
            (L + ("ln2", "scale"), (d,), "s", 0),
            (L + ("moe", "router"), (d, e), "r", d),
            (L + ("moe", "wi"), (e, d, f), "w", d),
            (L + ("moe", "wg"), (e, d, f), "w", d),
            (L + ("moe", "wo"), (e, f, d), "w", f),
        ]
    specs.append((("final_norm", "scale"), (d,), "s", 0))
    if not cfg["tie_word_embeddings"]:
        specs.append((("lm_head", "w"), (d, v), "w", d))
    return specs


def param_count(cfg: dict) -> int:
    return sum(math.prod(shape) for _p, shape, _k, _f in leaf_specs(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    d, h, kv, dh, f, e, n_layers, v = _sizes(cfg)
    return n_layers * 2 * kv * dh * 2


def page_bytes(cfg: dict, page_size: int) -> int:
    return page_size * (kv_bytes_per_token(cfg) + 4 * cfg["num_hidden_layers"])


def state_bytes_per_row(cfg: dict) -> int:
    return 0


def decode_step_work(cfg: dict, ctx_lens: Sequence[int]):
    """Each row runs its ``num_experts_per_tok`` experts; the step reads
    every expert once (with more rows than experts, each is routed to)."""
    d, h, kv, dh, f, e, n_layers, v = _sizes(cfg)
    k = cfg["num_experts_per_tok"]
    ctx = sum(int(c) for c in ctx_lens)
    per_token = n_layers * (2 * d * (h * dh + 2 * kv * dh) + 2 * h * dh * d
                            + 2 * d * e + k * 3 * 2 * d * f) + 2 * d * v
    flops = len(ctx_lens) * per_token + n_layers * 4 * h * dh * ctx
    nbytes = 2 * (param_count(cfg) - v * d) + ctx * kv_bytes_per_token(cfg)
    return float(flops), float(nbytes)
