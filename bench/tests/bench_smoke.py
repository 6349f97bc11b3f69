"""Smoke-size stand-ins for the cells' configuration and traffic files,
for driving the whole harness on the CPU in tests."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the widths of the program's qwen2-1.5b smoke preset; the pool takes
# a thousandth of the described chip's HBM, about 3,700 pages
CONFIG = {"hidden_size": 96, "num_attention_heads": 6,
          "num_key_value_heads": 2, "head_dim": 16,
          "intermediate_size": 192, "num_hidden_layers": 2,
          "vocab_size": 512, "attention_bias": True,
          "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
          "tie_word_embeddings": True, "program_arch": "qwen2-1.5b",
          "reference": "dense_gqa",
          "assumed": {"page_size": 16, "hbm_fill": 0.001,
                      "workspace_bytes": 0}}

# contexts of up to 370 tokens against a widest prompt bucket of 128:
# the steady start admits them through prefixes
REASON_FORK = {
    "max_len": 512,
    "prompt_tokens": {"dist": "uniform", "lo": 40, "hi": 120,
                      "distinct": 16},
    "reasoning_tokens": {"dist": "lognormal", "median": 100, "sigma": 0.6,
                         "lo": 40, "hi": 250},
    "trigger_every": {"dist": "uniform", "lo": 8, "hi": 30},
    "fork_tokens": {"dist": "uniform", "lo": 10, "hi": 40}}

# a smoke-size limit: at these widths sound runs read about 1e-3
LIMITS = {"checks": {"widest_gap": {"limit": 0.05}}}


def overrides(traffic=REASON_FORK):
    from repro.models.registry import get_smoke
    return {"config": CONFIG, "traffic": traffic,
            "program_cfg": get_smoke("qwen2-1.5b"),
            "device_kind": "TPU v5 lite", "limits": LIMITS}

# A size at which the float8 control separates from the program on the
# CPU: 4 layers of width 256 (seeds 21-26: the program's widest gap at
# most 0.0085, the control's at least 0.084).
MEDIUM = {"num_hidden_layers": 4, "hidden_size": 256,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 64, "intermediate_size": 512, "vocab_size": 4096}
MEDIUM_LIMITS = {"checks": {"widest_gap": {"limit": 0.03}}}


def medium_overrides():
    from repro.models.config import ModelConfig
    c = dict(CONFIG, **MEDIUM, assumed=dict(CONFIG["assumed"],
                                            hbm_fill=0.004))
    pc = ModelConfig(
        name="medium", family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=True, rope_theta=c["rope_theta"], tie_embeddings=True)
    return {**overrides(), "config": c, "program_cfg": pc,
            "limits": MEDIUM_LIMITS}
