"""Architecture registry: ``--arch <id>`` resolves through REGISTRY."""
from __future__ import annotations

import importlib

from repro.configs.base import (ArchConfig, LBGMConfig, MLAConfig, MoEConfig,
                                ShapeConfig, INPUT_SHAPES, param_count,
                                active_param_count)

_MODULES = {
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick_400b_a17b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "mistral-large-123b": "repro.configs.mistral_large_123b",
    "qwen3-1.7b": "repro.configs.qwen3_1_7b",
    "whisper-base": "repro.configs.whisper_base",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "qwen2-vl-2b": "repro.configs.qwen2_vl_2b",
    "yi-34b": "repro.configs.yi_34b",
    "deepseek-67b": "repro.configs.deepseek_67b",
    "kanana-2-30b-a3b": "repro.configs.kanana_2_30b_a3b",
    "paper-cnn": "repro.configs.paper_cnn",
    "paper-fcn": "repro.configs.paper_fcn",
}

ASSIGNED_ARCHS = [k for k in _MODULES if not k.startswith("paper-")]


def get_config(name: str) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[name]).CONFIG


def all_configs():
    return {name: get_config(name) for name in _MODULES}


__all__ = [
    "ArchConfig", "LBGMConfig", "MLAConfig", "MoEConfig", "ShapeConfig", "INPUT_SHAPES",
    "param_count", "active_param_count", "get_config", "all_configs",
    "ASSIGNED_ARCHS",
]
