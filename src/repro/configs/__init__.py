"""Architecture registry: the 10 assigned architectures (+ the paper's own
FNN-3 descriptor).  ``get_config(id)`` / ``--arch <id>`` resolve here."""
from __future__ import annotations

from repro.configs import (
    command_r_35b,
    deepseek_moe_16b,
    deepseek_v2_lite,
    gemma3_4b,
    jamba_15_large,
    llama32_1b,
    llava_next_34b,
    musicgen_medium,
    phi35_moe_42b,
    stablelm_16b,
    xlstm_125m,
)
from repro.configs.shapes import INPUT_SHAPES, InputShape, applicable, input_specs
from repro.models.config import ModelConfig

ARCHS = {
    c.CONFIG.name: c.CONFIG
    for c in (
        phi35_moe_42b, llama32_1b, stablelm_16b, gemma3_4b, jamba_15_large,
        musicgen_medium, llava_next_34b, command_r_35b, xlstm_125m,
        deepseek_moe_16b, deepseek_v2_lite,
    )
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def list_archs() -> list[str]:
    return sorted(ARCHS)


__all__ = ["ARCHS", "INPUT_SHAPES", "InputShape", "ModelConfig",
           "applicable", "get_config", "input_specs", "list_archs"]
