"""Config registry: ``get_arch_config("<id>")`` for every assigned
architecture (plus the paper's own RL configs in repro.rl)."""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    INPUT_SHAPES,
    LONG_CONTEXT_WINDOW,
    ArchConfig,
    GroupSpec,
    HybridConfig,
    MLAConfig,
    MoEConfig,
    ShapeConfig,
    SSMConfig,
)

_ARCH_MODULES = {
    "yi-34b": "yi_34b",
    "musicgen-medium": "musicgen_medium",
    "qwen2-7b": "qwen2_7b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "llama3.2-3b": "llama3_2_3b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "zamba2-7b": "zamba2_7b",
    "mamba2-780m": "mamba2_780m",
    "granite-3-8b": "granite_3_8b",
    "kanana-2-30b-a3b": "kanana_2_30b_a3b",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_arch_config(arch_id: str) -> ArchConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCH_IDS)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")
    return mod.get_config()


def arch_for_shape(cfg: ArchConfig, shape_name: str) -> ArchConfig:
    """Apply per-shape variants: dense/VLM/audio archs get the
    sliding-window attention variant for long_500k (sub-quadratic
    requirement); SSM/hybrid run natively."""
    if shape_name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        if cfg.sliding_window is None:
            return cfg.with_(sliding_window=LONG_CONTEXT_WINDOW)
    return cfg
