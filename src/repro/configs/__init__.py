"""Config registry: ``get_config(arch_id)`` for every assigned architecture."""

from typing import Dict, List

from .base import ModelConfig
from .shapes import INPUT_SHAPES, InputShape, shape_applicable, token_specs

from . import (deepseek_moe_16b, gemma3_1b, granite_3_8b, granite_8b,
               llama32_vision_90b, mamba2_780m, moonlight_16b_a3b,
               musicgen_large, qwen3_moe_30b_a3b, stablelm_3b, zamba2_1_2b)

_REGISTRY: Dict[str, ModelConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (deepseek_moe_16b, granite_3_8b, mamba2_780m, musicgen_large,
              qwen3_moe_30b_a3b, zamba2_1_2b, granite_8b, gemma3_1b,
              llama32_vision_90b, stablelm_3b, moonlight_16b_a3b)
}

ARCH_IDS: List[str] = sorted(_REGISTRY)


def get_config(arch: str) -> ModelConfig:
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch '{arch}'; available: {ARCH_IDS}")
    return _REGISTRY[arch]


__all__ = ["ModelConfig", "get_config", "ARCH_IDS", "INPUT_SHAPES",
           "InputShape", "shape_applicable", "token_specs"]
