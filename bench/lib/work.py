"""Operations and bytes the decode algorithm needs, from shapes alone.

Everything here is a function of the configuration file's sizes and the
live rows' context lengths.  It never reads the engine's ``max_len``,
``max_batch`` or ``Runtime``: a padded gather over the whole block table
is the program's choice, not work the algorithm needs, so a later change
that stops doing it is read against the same work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Per-chip peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, kv, dh, cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]


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


def least_time_s(flops: float, nbytes: float, kind: str) -> float:
    """The roofline: the larger of compute and memory time at peak."""
    pk = peaks(kind)
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
