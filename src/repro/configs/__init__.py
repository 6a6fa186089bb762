"""Architecture registry: ``--arch <id>`` resolution for every launcher.

ARCHS maps arch id -> full ModelConfig (the assigned published dims);
TINY_ARCHS maps arch id -> reduced same-family smoke config (CPU-runnable).
"""

from __future__ import annotations

from repro.configs import (
    base,
    dbrx_132b,
    deepseek_7b,
    deepseek_v2_lite,
    granite_moe_1b,
    internlm2_1_8b,
    llama32_vision_11b,
    mamba2_780m,
    minicpm3_4b,
    musicgen_medium,
    olmo_1b,
    recurrentgemma_9b,
)
from repro.configs.base import (  # noqa: F401
    ALL_SHAPES,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    TRAIN_4K,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RGLRUConfig,
    SSMConfig,
    ShapeConfig,
    TrainConfig,
    YaRNConfig,
    shape_applicable,
)

_MODULES = (
    mamba2_780m,
    musicgen_medium,
    dbrx_132b,
    granite_moe_1b,
    olmo_1b,
    deepseek_7b,
    minicpm3_4b,
    internlm2_1_8b,
    recurrentgemma_9b,
    llama32_vision_11b,
    deepseek_v2_lite,
)

ARCHS: dict[str, ModelConfig] = {m.CONFIG.name: m.CONFIG for m in _MODULES}
TINY_ARCHS: dict[str, ModelConfig] = {m.CONFIG.name: m.TINY for m in _MODULES}
SHAPES: dict[str, ShapeConfig] = {s.name: s for s in ALL_SHAPES}


def get_arch(name: str, tiny: bool = False) -> ModelConfig:
    table = TINY_ARCHS if tiny else ARCHS
    if name not in table:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(table)}")
    return table[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES)}")
    return SHAPES[name]
