"""Operations and bytes the decode algorithm needs, from shapes alone.

Everything here is a function of the configuration file's sizes and the
live rows' context lengths.  It never reads the engine's ``max_len``,
``max_batch`` or ``Runtime``: a padded gather over the whole block table
is the program's choice, not work the algorithm needs, so a later change
that stops doing it is read against the same work.  The counts of a
family of models are its arch module's (``bench/arch/<arch>.py``); this
module keeps the peaks and the roofline.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

from bench.lib import arch

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Per-chip peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def param_count(cfg: dict) -> int:
    """Parameters held on this chip."""
    return arch.module(cfg).param_count(cfg)


def decode_step_work(cfg: dict, ctx_lens: Sequence[int]):
    """(flops, bytes) that one decode step of ``len(ctx_lens)`` live rows
    needs."""
    return arch.module(cfg).decode_step_work(cfg, ctx_lens)


def least_time_s(flops: float, nbytes: float, kind: str) -> float:
    """The roofline: the larger of compute and memory time at peak."""
    pk = peaks(kind)
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
