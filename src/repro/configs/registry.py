"""Architecture registry: full configs (assignment-exact) + reduced smoke
configs (same family, tiny) for CPU tests.

``get_config(arch)`` / ``get_smoke_config(arch)`` / ``ARCHS``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from .base import ModelConfig
from . import (
    gemma3_4b,
    granite_20b,
    llama3_2_3b,
    moonlight_16b_a3b,
    paper_llama3_moe,
    qwen2_vl_2b,
    qwen3_8b,
    qwen3_moe_235b_a22b,
    whisper_large_v3,
    xlstm_125m,
    zamba2_7b,
)

_MODULES = {
    "xlstm-125m": xlstm_125m,
    "qwen3-moe-235b-a22b": qwen3_moe_235b_a22b,
    "moonlight-16b-a3b": moonlight_16b_a3b,
    "qwen2-vl-2b": qwen2_vl_2b,
    "qwen3-8b": qwen3_8b,
    "llama3.2-3b": llama3_2_3b,
    "granite-20b": granite_20b,
    "gemma3-4b": gemma3_4b,
    "whisper-large-v3": whisper_large_v3,
    "zamba2-7b": zamba2_7b,
    "paper-llama3-moe": paper_llama3_moe,
}

ARCHS = [k for k in _MODULES if k != "paper-llama3-moe"]
ALL_CONFIGS = list(_MODULES)


def get_config(arch: str) -> ModelConfig:
    return _MODULES[arch].CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _MODULES[arch].SMOKE


def supports_decode(arch: str) -> bool:
    return True  # all ten include a decoder (whisper is enc-dec)


def supports_long_context(arch: str) -> bool:
    """long_500k runs only for SSM/hybrid/linear-attention archs (see
    DESIGN.md §Shape-cell skips)."""
    fam = get_config(arch).family
    return fam in ("xlstm", "hybrid")
