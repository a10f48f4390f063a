"""Architecture registry: ``--arch <id>`` lookup for full and reduced configs."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, TrainConfig

# arch id -> module name
_ARCH_MODULES = {
    "qwen2.5-3b": "qwen2_5_3b",
    "qwen2-7b": "qwen2_7b",
    "qwen3-14b": "qwen3_14b",
    "qwen3-32b": "qwen3_32b",
    "whisper-large-v3": "whisper_large_v3",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "mixtral-8x7b": "mixtral_8x7b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "xlstm-125m": "xlstm_125m",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def _module(arch: str):
    try:
        mod = _ARCH_MODULES[arch]
    except KeyError:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_reduced_config(arch: str) -> ModelConfig:
    return _module(arch).reduced()


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def arch_shape_cells(include_skipped: bool = False):
    """All (arch, shape) dry-run cells; long_500k only where sub-quadratic.

    Encoder-decoder archs keep decode shapes (the decoder decodes);
    pure full-attention archs skip long_500k per the assignment.
    """
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            skip = shape.name == "long_500k" and not cfg.supports_long_context
            if skip and not include_skipped:
                continue
            cells.append((arch, shape.name) if not include_skipped
                         else (arch, shape.name, skip))
    return cells


__all__ = [
    "ARCH_IDS", "SHAPES", "ModelConfig", "ShapeConfig", "TrainConfig",
    "get_config", "get_reduced_config", "get_shape", "arch_shape_cells",
]
