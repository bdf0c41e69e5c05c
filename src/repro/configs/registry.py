"""Architecture registry: ``--arch <id>`` resolution for all launchers."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig

_MODULES: Dict[str, str] = {
    "glm4-9b": "glm4_9b",
    "minicpm3-4b": "minicpm3_4b",
    "qwen3-4b": "qwen3_4b",
    "stablelm-1.6b": "stablelm_1_6b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "whisper-medium": "whisper_medium",
    "xlstm-125m": "xlstm_125m",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def list_archs() -> List[str]:
    return list(_MODULES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; available: {list_archs()}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.SMOKE if smoke else mod.CONFIG

