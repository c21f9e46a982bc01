"""Architecture registry: --arch <id> resolves here."""

from __future__ import annotations

import importlib

from repro.configs.base import (
    ALL_SHAPES,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    TRAIN_4K,
    ModelConfig,
    MoEConfig,
    ShapeSpec,
)

_MODULES = {
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "internvl2-2b": "internvl2_2b",
    "whisper-tiny": "whisper_tiny",
    "llama3.2-1b": "llama3_2_1b",
    "glm4-9b": "glm4_9b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "gemma3-4b": "gemma3_4b",
    "xlstm-350m": "xlstm_350m",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}

ARCH_IDS = tuple(_MODULES)

# archs with a sub-quadratic decode path: the only ones that run long_500k
SUBQUADRATIC = ("xlstm-350m", "recurrentgemma-2b", "gemma3-4b")


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.SMOKE if smoke else mod.CONFIG


def shapes_for(arch: str) -> tuple:
    """The assigned shape cells that apply to this architecture.

    ``long_500k`` needs sub-quadratic attention: run for SSM/hybrid and for
    gemma3 (5:1 local:global — decode is dominated by the windowed local
    layers); skip for pure full-attention archs (recorded in DESIGN.md).
    """
    shapes = [TRAIN_4K, PREFILL_32K, DECODE_32K]
    if arch in SUBQUADRATIC:
        shapes.append(LONG_500K)
    return tuple(shapes)


def all_cells() -> list:
    """Every (arch, shape) cell in the assignment (40 incl. skips; the
    skipped long_500k cells are reported as skips, not silently dropped)."""
    cells = []
    for a in ARCH_IDS:
        for s in ALL_SHAPES:
            cells.append((a, s, s in shapes_for(a)))
    return cells
