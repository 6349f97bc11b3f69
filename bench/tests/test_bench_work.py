"""Work counts and peaks: from the configuration and the live rows'
context lengths alone."""
import inspect
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.arch import dense_gqa  # noqa: E402
from bench.lib import work  # noqa: E402

QWEN2 = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())


def test_qwen2_step_matches_hand_arithmetic():
    flops, nbytes = work.decode_step_work(QWEN2, [1000, 3000])
    # per token: q,k,v (1536 x (1536 + 2*256)), o (1536 x 1536), the
    # SwiGLU MLP (3 x 1536 x 8960), 2 FLOPs a multiply-add, 28 layers,
    # then the tied head (1536 x 151936)
    per_tok = 28 * (2 * 1536 * 2048 + 2 * 1536 * 1536 + 6 * 1536 * 8960) \
        + 2 * 1536 * 151936
    assert per_tok == 3_087_138_816
    attn = 28 * 4 * 12 * 128 * (1000 + 3000)
    assert flops == 2 * per_tok + attn == 6_862_405_632
    params = 151936 * 1536 + 28 * (
        1536 * 12 * 128 * 2 + 1536 * 2 * 128 * 2 + 12 * 128 + 2 * 2 * 128
        + 3 * 1536 * 8960 + 2 * 1536) + 1536
    assert params == work.param_count(QWEN2) == 1_543_714_304
    kv_tok = 28 * 2 * 2 * 128 * 2
    assert kv_tok == dense_gqa.kv_bytes_per_token(QWEN2) == 28_672
    assert nbytes == 2 * params + 4000 * kv_tok


class Watched(dict):
    def __init__(self, *a):
        super().__init__(*a)
        self.read = set()

    def __getitem__(self, k):
        self.read.add(k)
        return super().__getitem__(k)

    def get(self, k, d=None):
        self.read.add(k)
        return super().get(k, d)


def test_count_reads_only_shapes_and_context_lengths():
    assert list(inspect.signature(work.decode_step_work).parameters) == [
        "cfg", "ctx_lens"]
    cfg = Watched(QWEN2)
    base = work.decode_step_work(cfg, [10, 20, 30])
    assert cfg.read <= {
        "arch", "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "num_hidden_layers", "vocab_size",
        "attention_bias", "qk_norm", "tie_word_embeddings"}
    padded = dict(QWEN2, max_len=10240, max_batch=40, num_pages=1)
    assert work.decode_step_work(padded, [10, 20, 30]) == base
    # more rows or longer contexts cost more; empty slots cost nothing
    assert work.decode_step_work(QWEN2, [10, 20, 31])[1] > base[1]
    assert work.decode_step_work(QWEN2, [10, 20, 30, 1])[0] > base[0]


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_least_time_is_the_larger_bound():
    flops, nbytes = work.decode_step_work(QWEN2, [4096] * 24)
    t = work.least_time_s(flops, nbytes, "TPU v5 lite")
    assert t == max(flops / 197e12, nbytes / 819e9)
    assert t == nbytes / 819e9          # decode is bound by bytes
