"""Launch-layer units: collective parsing, memory model, cell matrix,
compile-cache placement."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.dryrun import parse_collectives, _affine, model_flops
from repro.launch.memmodel import estimate_memory
from repro.launch.shapes import (SHAPES, all_cells, input_specs,
                                 runnable_cells, skip_reason)
from repro.models.layers import Runtime
from repro.models.registry import ARCH_IDS, get_config
from repro.distributed.sharding import SERVE_RULES, TRAIN_RULES

HLO = """
ENTRY %main {
  %ag = bf16[32,1024] all-gather(bf16[2,1024] %x), replica_groups=[32,16]<=[512], dimensions={0}
  %ar = f32[256,256] all-reduce(f32[256,256] %y), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = bf16[8,128] reduce-scatter(bf16[128,128] %z), replica_groups=[32,16]<=[512], dimensions={0}
  %cp = f32[64] collective-permute(f32[64] %w), source_target_pairs={{0,1}}
}
"""


def test_parse_collectives_kinds_and_bytes():
    res = parse_collectives(HLO, 512)
    kinds = {o["op"] for o in res["ops"]}
    assert kinds == {"all-gather", "all-reduce", "reduce-scatter",
                     "collective-permute"}
    ag = next(o for o in res["ops"] if o["op"] == "all-gather")
    assert ag["group"] == 16
    assert ag["bytes"] == 32 * 1024 * 2
    assert ag["moved"] == pytest.approx(ag["bytes"] * 15 / 16)
    ar = next(o for o in res["ops"] if o["op"] == "all-reduce")
    assert ar["group"] == 4
    assert ar["moved"] == pytest.approx(2 * 256 * 256 * 4 * 3 / 4)
    assert res["moved_per_device"] > 0


def test_affine_extrapolation():
    # cost(L) = a + b*L: recover from two samples exactly
    a, b = 7.0, 3.0
    lo, hi = a + b * 2, a + b * 4
    assert _affine(lo, hi, 2, 4, 62) == pytest.approx(a + b * 62)


def test_cell_matrix_counts():
    assert len(all_cells()) == 40
    assert len(runnable_cells()) == 32          # 8 principled skips
    skips = [c for c in all_cells() if skip_reason(*c)]
    assert all(s == "long_500k" for _, s in skips)
    assert {a for a, _ in skips} == {
        "deepseek-coder-33b", "qwen3-4b", "qwen2-1.5b", "starcoder2-3b",
        "musicgen-medium", "phi3.5-moe-42b-a6.6b",
        "llama4-scout-17b-a16e", "internvl2-1b"}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_complete(arch):
    for shape in SHAPES:
        if skip_reason(arch, shape):
            continue
        specs = input_specs(arch, shape)
        assert specs, (arch, shape)
        sp = SHAPES[shape]
        if sp.kind == "train":
            assert specs["labels"].shape == (sp.global_batch, sp.seq_len)
        if sp.kind == "decode":
            assert specs["tokens"].shape == (sp.global_batch, 1)
            assert len(specs["cache"]) == get_config(arch).num_layers


def test_memory_model_fits_judgments():
    mesh = {"data": 16, "model": 16}
    rt = Runtime(attn_impl="chunked", q_chunk=2048, remat="layer",
                 ce_chunks=8)
    # llama4 fits; deepseek is the one knowingly-over cell (16.71 GiB,
    # -4.5%: EXPERIMENTS.md SS Dry-run) — assert both judgments exactly
    mm = estimate_memory(get_config("llama4-scout-17b-a16e"), "train_4k",
                         mesh, TRAIN_RULES, rt)
    assert mm["total"] < 16 * 2 ** 30
    mm = estimate_memory(get_config("deepseek-coder-33b"), "train_4k",
                         mesh, TRAIN_RULES, rt)
    assert 16 * 2 ** 30 < mm["total"] < 17.5 * 2 ** 30
    # optimizer state dominates params 4:1 (fp32 m+v vs bf16)
    assert mm["optimizer"] == pytest.approx(4 * mm["params"])
    # decode: deepseek KV cache at 32k fits when seq+batch sharded
    cfg = get_config("deepseek-coder-33b")
    mm = estimate_memory(cfg, "decode_32k", mesh, SERVE_RULES, Runtime())
    assert mm["kv_cache"] < 6 * 2 ** 30
    assert mm["total"] < 16 * 2 ** 30


def test_model_flops_scaling():
    cfg = get_config("qwen2-1.5b")
    # train_4k and prefill_32k process the same 1.05M tokens; train is
    # fwd+bwd = ~3x fwd minus the attention-context difference
    tr, pf = model_flops(cfg, "train_4k"), model_flops(cfg, "prefill_32k")
    assert 1.5 * pf < tr < 3.1 * pf
    assert model_flops(cfg, "decode_32k") < pf


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.CHECKOUT_CACHE == root / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_is_written_and_nothing_set(tmp_path):
    """With the variable set, the program sets nothing itself and the
    compiled executable lands in the named directory."""
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "print(enable_compile_cache())\n"
            "assert jax.config.jax_compilation_cache_dir == before\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x + 1)(1).block_until_ready()\n")
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing written to the cache dir"
