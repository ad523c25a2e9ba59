"""Architecture registry: `--arch <id>` resolution.

Ten assigned architectures, granite-4.0-h-micro (the served Mamba2 +
GQA hybrid) and the paper's own evaluation CNN.
"""
from __future__ import annotations

import importlib

from repro.configs.base import (ArchConfig, ModelCfg, MoECfg, RWKVCfg,
                                SHAPES, SSMCfg, ShapeCfg, TDExecCfg, TrainCfg)

_MODULES = {
    "granite-8b": "repro.configs.granite_8b",
    "qwen2.5-3b": "repro.configs.qwen2_5_3b",
    "qwen3-8b": "repro.configs.qwen3_8b",
    "qwen3-4b": "repro.configs.qwen3_4b",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "rwkv6-1.6b": "repro.configs.rwkv6_1_6b",
    "granite-4.0-h-micro": "repro.configs.granite_4_0_h_micro",
}

ARCH_NAMES = tuple(_MODULES)

# pure full-attention archs skip the long_500k cell (sub-quadratic required)
LONG_CONTEXT_ARCHS = ("zamba2-1.2b", "rwkv6-1.6b")


def get(name: str) -> ArchConfig:
    mod = importlib.import_module(_MODULES[name])
    return mod.CONFIG


def get_smoke(name: str) -> ArchConfig:
    mod = importlib.import_module(_MODULES[name])
    return mod.smoke()


def cells(include_skips: bool = True):
    """All 40 (arch x shape) cells; skipped cells flagged."""
    out = []
    for a in ARCH_NAMES:
        for s in SHAPES.values():
            skip = (s.name == "long_500k" and a not in LONG_CONTEXT_ARCHS)
            if include_skips or not skip:
                out.append((a, s.name, skip))
    return out


__all__ = ["ArchConfig", "ModelCfg", "MoECfg", "RWKVCfg", "SSMCfg",
           "ShapeCfg", "TDExecCfg", "TrainCfg", "SHAPES", "ARCH_NAMES",
           "LONG_CONTEXT_ARCHS", "get", "get_smoke", "cells"]
