"""Architecture registry.

Each ``configs/<arch>.py`` defines ``CONFIG`` (the exact published
configuration) and ``SMOKE`` (a reduced same-family configuration for CPU
tests).  ``get(arch)`` / ``get_smoke(arch)`` resolve by id; ``ARCHS`` lists
all supported architectures.

``long_500k`` applicability (DESIGN.md Sec. 5): sub-quadratic decode memory
is required, so only the SSM/hybrid/windowed archs run it; pure
full-attention archs skip it (their KV cache alone exceeds the budget).
"""

from __future__ import annotations

import importlib
from typing import List

from ..models.config import ModelConfig, SHAPES, SMOKE_SHAPES, ShapeConfig

ARCHS: List[str] = [
    "seamless_m4t_medium",
    "zamba2_7b",
    "minitron_4b",
    "granite_8b",
    "stablelm_3b",
    "llama3_2_1b",
    "mixtral_8x7b",
    "granite_moe_3b_a800m",
    "phi3_vision_4_2b",
    "xlstm_350m",
    "deepseek_v2_lite",
    "granite_4_0_h_small",
]

# Archs whose long_500k cell runs (sub-quadratic decode state).
LONG_CONTEXT_OK = {"zamba2_7b", "mixtral_8x7b", "xlstm_350m"}


def _module(arch: str):
    return importlib.import_module(f"repro.configs.{arch}")


def get(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke(arch: str) -> ModelConfig:
    return _module(arch).SMOKE


def shapes_for(arch: str, smoke: bool = False):
    """The (shape -> ShapeConfig) cells this arch runs, with documented
    skips applied."""
    table = SMOKE_SHAPES if smoke else SHAPES
    out = {}
    for name, sc in table.items():
        if name == "long_500k" and arch not in LONG_CONTEXT_OK:
            continue
        out[name] = sc
    return out
