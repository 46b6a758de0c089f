"""Config registry: ``get_config(arch_id)`` / ``get_shape(shape_id)``.

Arch ids use the assignment's dashed names (e.g. ``nemotron-4-15b``);
module names use underscores.
"""
from repro.config import ModelConfig, ShapeConfig
from repro.configs import (
    chameleon_34b,
    dbrx_132b,
    deepseek_v2_236b,
    gemma_2b,
    hymba_1_5b,
    mamba2_130m,
    musicgen_medium,
    nemotron_3_nano_30b_a3b,
    nemotron_4_15b,
    qwen2_1_5b,
    yi_34b,
)
from repro.configs.paper_models import PAPER_NETS  # noqa: F401
from repro.configs.shapes import SHAPES

_MODULES = (
    nemotron_4_15b, qwen2_1_5b, gemma_2b, yi_34b, dbrx_132b,
    musicgen_medium, mamba2_130m, chameleon_34b, deepseek_v2_236b, hymba_1_5b,
    nemotron_3_nano_30b_a3b,
)

REGISTRY = {m.CONFIG.name: m.CONFIG for m in _MODULES}
ARCH_IDS = tuple(REGISTRY)


def get_config(arch: str) -> ModelConfig:
    key = arch.replace("_", "-")
    if key not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(REGISTRY)}")
    return REGISTRY[key]


def get_shape(shape: str) -> ShapeConfig:
    if shape not in SHAPES:
        raise KeyError(f"unknown shape {shape!r}; available: {sorted(SHAPES)}")
    return SHAPES[shape]
