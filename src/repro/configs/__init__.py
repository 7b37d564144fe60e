"""Architecture registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

import importlib

from repro.configs.base import (INPUT_SHAPES, EncoderConfig, InputShape,
                                ModelConfig, MoEConfig, SSMConfig, reduced)

_MODULES = {
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "qwen2-7b": "qwen2_7b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "qwen3-1.7b": "qwen3_1_7b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "stablelm-1.6b": "stablelm_1_6b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "mamba2-1.3b": "mamba2_1_3b",
    "llama-3.2-vision-90b": "llama_3_2_vision_90b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

# the architectures every --all sweep and smoke test runs
ARCH_IDS = tuple(_MODULES)

# extra variants (selectable via --arch, excluded from ARCH_IDS)
_MODULES["qwen2-7b-kv8"] = "qwen2_7b_kv8"


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def all_configs():
    return {a: get_config(a) for a in ARCH_IDS}
