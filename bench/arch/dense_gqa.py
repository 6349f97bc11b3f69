"""Shapes of the dense GQA decoder (the Qwen2 / Qwen3 block): attention
with grouped K/V heads, optional q/k/v bias and q/k norm, a SwiGLU MLP,
every layer alike, K/V paged in every layer.

A configuration file names its architecture, ``"arch": "<name>"``, and
the harness loads ``bench/arch/<name>.py`` (``bench/lib/arch.py``).
Every such module provides these functions of the configuration file's
sizes; none reads the engine's ``max_len``, ``max_batch`` or
``Runtime``:

``widths(cfg)``
    the program ``ModelConfig`` fields and the values they must have;
    ``run.check_widths`` refuses a program configuration that differs.
``leaf_specs(cfg)``
    the weight tree, a list of ``(path, shape, kind, fan_in)`` in the
    order that gives each leaf its ``fold_in`` index, so a configuration
    already in the benchmark keeps its order, and its weights.  ``kind``
    is one of ``bench/lib/weights.py``'s (``w``, ``e``, ``b``, ``s``) or
    a key of the module's own ``LEAF_KINDS``.
``LEAF_KINDS`` (optional)
    extra leaf kinds: ``{kind: draw(key, shape, fan_in)}``, each an
    array in the type the program serves it in (a decay's log kept in
    its range, a float32 router).
``param_count(cfg)``
    the parameters held on this chip, each two bytes in bfloat16.
``page_bytes(cfg, page_size)``
    one page of the paged cache, over the layers that page K/V only,
    each position's ``kv_pos`` included.
``state_bytes_per_row(cfg)``
    the fixed-size state one batch slot holds besides its pages
    (a recurrent layer's state); the pool leaves ``max_batch`` of them.
``decode_step_work(cfg, ctx_lens)``
    ``(flops, bytes)`` that one decode step of ``len(ctx_lens)`` live
    rows needs, read by the roofline and MFU readers.
"""
from __future__ import annotations

from typing import Sequence


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, kv, dh, cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]


def widths(cfg: dict) -> dict:
    d, h, kv, dh, f, n_layers, vocab = _sizes(cfg)
    return {
        "num_layers": n_layers, "d_model": d,
        "num_heads": h, "num_kv_heads": kv,
        "head_dim": dh,
        "d_ff": f,
        "vocab_size": vocab,
        "qkv_bias": bool(cfg.get("attention_bias", False)),
        "qk_norm": bool(cfg.get("qk_norm", False)),
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
    }


def leaf_specs(cfg: dict):
    """``embed.tokens``, ``layers[i].{ln1,attn,ln2,mlp}``,
    ``final_norm``; norm scales and biases are drawn too, not left at
    one and zero, so that the comparison with the reference sees whether
    the program applies them."""
    d, h, kv, dh, f, n_layers, v = _sizes(cfg)
    # kind: w (bf16 normal / sqrt(fan_in)), b (bf16 bias), s (f32 scale
    # near 1), e (bf16 embedding)
    specs = [(("embed", "tokens"), (v, d), "e", d)]
    for i in range(n_layers):
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


def param_count(cfg: dict) -> int:
    """Parameters of a dense GQA decoder with a SwiGLU MLP."""
    d, h, kv, dh, f, n_layers, vocab = _sizes(cfg)
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    if cfg.get("attention_bias", False):
        attn += h * dh + 2 * kv * dh
    if cfg.get("qk_norm", False):
        attn += 2 * dh
    layer = attn + 3 * d * f + 2 * d
    head = 0 if cfg.get("tie_word_embeddings", False) else d * vocab
    return vocab * d + n_layers * layer + d + head


def weight_bytes_per_step(cfg: dict, bytes_per_param: int = 2) -> int:
    """Weights one decode step reads once: every layer, the final norm
    and the LM head.  The embedding table is read only at the step's
    rows, which is negligible; with tied embeddings the head IS the
    table, read whole once."""
    d, h, kv, dh, f, n_layers, vocab = _sizes(cfg)
    tied = cfg.get("tie_word_embeddings", False)
    n = param_count(cfg) - (0 if tied else vocab * d)
    return n * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_elem: int = 2) -> int:
    d, h, kv, dh, f, n_layers, vocab = _sizes(cfg)
    return n_layers * 2 * kv * dh * bytes_per_elem


def page_bytes(cfg: dict, page_size: int) -> int:
    """K and V in bfloat16 and an int32 ``kv_pos`` a position, in every
    layer."""
    return page_size * (kv_bytes_per_token(cfg) + 4 * cfg["num_hidden_layers"])


def state_bytes_per_row(cfg: dict) -> int:
    return 0


def decode_step_work(cfg: dict, ctx_lens: Sequence[int]):
    """(flops, bytes) that one decode step of ``len(ctx_lens)`` live rows
    needs: each row reads its own K/V over its real context, the weights
    are read once, and each row does the matmuls of every projection,
    the MLP, the LM head and attention over its context."""
    d, h, kv, dh, f, n_layers, vocab = _sizes(cfg)
    rows = len(ctx_lens)
    ctx = sum(int(c) for c in ctx_lens)
    per_token_matmul = n_layers * (
        2 * d * (h * dh + 2 * kv * dh) + 2 * h * dh * d + 3 * 2 * d * f
    ) + 2 * d * vocab
    attn = n_layers * 4 * h * dh * ctx          # QK^T and AV per row
    flops = rows * per_token_matmul + attn
    nbytes = weight_bytes_per_step(cfg) + ctx * kv_bytes_per_token(cfg)
    return float(flops), float(nbytes)
