"""Architecture registry: --arch <id> -> ModelConfig (full or smoke)."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig

ARCH_IDS: List[str] = [
    "deepseek-coder-33b",
    "qwen3-4b",
    "qwen2-1.5b",
    "starcoder2-3b",
    "musicgen-medium",
    "mamba2-2.7b",
    "phi3.5-moe-42b-a6.6b",
    "llama4-scout-17b-a16e",
    "internvl2-1b",
    "recurrentgemma-2b",
]

_MODULES: Dict[str, str] = {a: a.replace("-", "_").replace(".", "_")
                            for a in ARCH_IDS}


def _load(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _load(arch).CONFIG


def get_smoke(arch: str) -> ModelConfig:
    return _load(arch).SMOKE


# "smoke": the toy widths tests and golden traces use; "full": the
# published config, as the chip runs it
SIZES = ("smoke", "full")


def get_sized(arch: str, size: str) -> ModelConfig:
    if size not in SIZES:
        raise ValueError(f"unknown size {size!r}; known: {SIZES}")
    return get_config(arch) if size == "full" else get_smoke(arch)


def list_archs() -> List[str]:
    return list(ARCH_IDS)
