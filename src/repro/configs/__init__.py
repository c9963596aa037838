"""Architecture registry: one module per assigned architecture (+ the
paper's own DeepSeek-Distill-Qwen models), each exporting

    CONFIG        — the exact published configuration
    smoke_config()— a reduced same-family config for CPU smoke tests

Select with ``--arch <id>`` in the launchers; ``get_config``/``list_archs``
are the programmatic API.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.api import ModelConfig

_ARCH_MODULES = {
    # --- assigned architectures (10) ---
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "starcoder2-15b": "starcoder2_15b",
    "yi-34b": "yi_34b",
    "qwen2.5-3b": "qwen2_5_3b",
    "whisper-small": "whisper_small",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "grok-1-314b": "grok_1_314b",
    "xlstm-1.3b": "xlstm_1_3b",
    "internvl2-2b": "internvl2_2b",
    "hymba-1.5b": "hymba_1_5b",
    # --- the paper's evaluation models ---
    "qwen-distill-1.5b": "qwen_distill_1_5b",
    "qwen-distill-7b": "qwen_distill_7b",
    "qwen-distill-14b": "qwen_distill_14b",
    # --- the paged engine's other benchmark models ---
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ASSIGNED_ARCHS: List[str] = list(_ARCH_MODULES)[:10]
PAPER_ARCHS: List[str] = list(_ARCH_MODULES)[10:13]


def list_archs() -> List[str]:
    return list(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.smoke_config()
