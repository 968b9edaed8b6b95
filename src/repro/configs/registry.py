"""Architecture registry: ``get_arch(id)`` / ``list_archs()``.

IDs match the assignment table exactly (``--arch <id>`` in launchers).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ArchConfig

_MODULES: Dict[str, str] = {
    "stablelm-12b": "repro.configs.stablelm_12b",
    "arctic-480b": "repro.configs.arctic_480b",
    "hymba-1.5b": "repro.configs.hymba_1p5b",
    "qwen1.5-110b": "repro.configs.qwen15_110b",
    "pixtral-12b": "repro.configs.pixtral_12b",
    "gemma-7b": "repro.configs.gemma_7b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "qwen3-1.7b": "repro.configs.qwen3_1p7b",
    "falcon-mamba-7b": "repro.configs.falcon_mamba_7b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    # paper's own experiment backbone (not in the assigned pool)
    "lenet": "repro.configs.lenet_fmnist",
}

ASSIGNED_ARCHS: List[str] = [k for k in _MODULES if k != "lenet"]


def get_arch(arch_id: str) -> ArchConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(_MODULES[arch_id])
    return mod.CONFIG


def list_archs() -> List[str]:
    return list(ASSIGNED_ARCHS)
