"""Architecture/config registry.

``get_config("mixtral-8x22b")`` returns the full assigned config;
``get_smoke_config`` the reduced same-family variant used by CPU smoke tests.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    INPUT_SHAPES,
    EncoderConfig,
    ExitConfig,
    InputShape,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    smoke_variant,
)

# arch-id -> module name under repro.configs
_REGISTRY: Dict[str, str] = {
    "deepseek-coder-33b": "deepseek_coder_33b",
    "granite-3-2b": "granite_3_2b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "qwen3-1.7b": "qwen3_1_7b",
    "qwen1.5-32b": "qwen1_5_32b",
    "rwkv6-3b": "rwkv6_3b",
    "zamba2-1.2b": "zamba2_1_2b",
    "mixtral-8x22b": "mixtral_8x22b",
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe_42b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    # the paper's own testbed geometry (not part of the assigned 10)
    "elasticbert12": "elasticbert12",
    # a benchmark configuration (not part of the assigned 10)
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}

ASSIGNED_ARCHS: List[str] = [a for a in _REGISTRY
                             if a not in ("elasticbert12",
                                          "granite-4.0-h-micro")]


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}")
    mod = importlib.import_module(f"repro.configs.{_REGISTRY[arch_id]}")
    return mod.CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    return smoke_variant(get_config(arch_id))


def get_input_shape(name: str) -> InputShape:
    if name not in INPUT_SHAPES:
        raise KeyError(f"unknown input shape {name!r}; known: {sorted(INPUT_SHAPES)}")
    return INPUT_SHAPES[name]


def list_archs() -> List[str]:
    return list(_REGISTRY)
