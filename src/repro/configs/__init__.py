"""Config registry: ``get_config(arch_id)`` / ``get_smoke(arch_id)``."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (
    ArchConfig, MLAConfig, MoEConfig, SSMConfig, XLSTMConfig, YarnConfig,
    INPUT_SHAPES, ShapeSpec, param_count,
)

_MODULES = {
    "deepseek-v3-671b":       "repro.configs.deepseek_v3_671b",
    "nemotron-4-340b":        "repro.configs.nemotron_4_340b",
    "zamba2-7b":              "repro.configs.zamba2_7b",
    "xlstm-350m":             "repro.configs.xlstm_350m",
    "deepseek-67b":           "repro.configs.deepseek_67b",
    "seamless-m4t-medium":    "repro.configs.seamless_m4t_medium",
    "command-r-35b":          "repro.configs.command_r_35b",
    "qwen2-vl-7b":            "repro.configs.qwen2_vl_7b",
    "llama4-scout-17b-a16e":  "repro.configs.llama4_scout_17b_a16e",
    "starcoder2-3b":          "repro.configs.starcoder2_3b",
    "mellum2-12b-a2.5b":      "repro.configs.mellum2_12b_a2_5b",
}

# (arch, shape) combos intentionally skipped, with reasons (DESIGN.md §4).
SKIPS: Dict[tuple, str] = {
    ("deepseek-v3-671b", "long_500k"):
        "pure full-attention (MLA) arch; no windowed variant claimed",
    ("seamless-m4t-medium", "long_500k"):
        "enc-dec full attention; 500k-frame decode out of scope",
    ("qwen2-vl-7b", "long_500k"):
        "pure full-attention arch; no windowed variant claimed",
}


def list_archs() -> List[str]:
    return list(_MODULES)


def get_config(arch_id: str) -> ArchConfig:
    return importlib.import_module(_MODULES[arch_id]).CONFIG


def get_smoke(arch_id: str) -> ArchConfig:
    return importlib.import_module(_MODULES[arch_id]).smoke()


def combos(include_skips: bool = False):
    """All (arch_id, shape_name) dry-run combos."""
    out = []
    for a in _MODULES:
        for s in INPUT_SHAPES:
            if not include_skips and (a, s) in SKIPS:
                continue
            out.append((a, s))
    return out


__all__ = [
    "ArchConfig", "MLAConfig", "MoEConfig", "SSMConfig", "XLSTMConfig",
    "YarnConfig",
    "INPUT_SHAPES", "ShapeSpec", "param_count", "SKIPS",
    "list_archs", "get_config", "get_smoke", "combos",
]
