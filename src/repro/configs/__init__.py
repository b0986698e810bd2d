"""Config registry: 10 assigned architectures (+ the paper's GPT-NeoX case
study and Granite 4.0-H Small), 4 benchmark shapes, and the (arch x shape)
applicability matrix."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from repro.configs.base import (  # noqa: F401
    ArchConfig,
    BlockSpec,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES,
    ShapeConfig,
    TRAIN_4K,
    get_shape,
    smoke_shape,
)

from repro.configs.mamba2_2p7b import CONFIG as MAMBA2_2P7B
from repro.configs.qwen2p5_3b import CONFIG as QWEN2P5_3B
from repro.configs.gemma2_2b import CONFIG as GEMMA2_2B
from repro.configs.llama3p2_3b import CONFIG as LLAMA3P2_3B
from repro.configs.gemma_2b import CONFIG as GEMMA_2B
from repro.configs.jamba_v0p1_52b import CONFIG as JAMBA_52B
from repro.configs.seamless_m4t_medium import CONFIG as SEAMLESS_M4T
from repro.configs.kimi_k2_1t import CONFIG as KIMI_K2
from repro.configs.llama4_maverick_400b import CONFIG as LLAMA4_MAVERICK
from repro.configs.internvl2_2b import CONFIG as INTERNVL2_2B
from repro.configs.gptneox_1b import CONFIG as GPTNEOX_1B
from repro.configs.granite4_h_small import CONFIG as GRANITE4_H_SMALL

# The 10 assigned architectures, in the task-spec order.
ASSIGNED: Tuple[ArchConfig, ...] = (
    MAMBA2_2P7B,
    QWEN2P5_3B,
    GEMMA2_2B,
    LLAMA3P2_3B,
    GEMMA_2B,
    JAMBA_52B,
    SEAMLESS_M4T,
    KIMI_K2,
    LLAMA4_MAVERICK,
    INTERNVL2_2B,
)

REGISTRY: Dict[str, ArchConfig] = {c.name: c for c in ASSIGNED}
REGISTRY[GPTNEOX_1B.name] = GPTNEOX_1B
REGISTRY[GRANITE4_H_SMALL.name] = GRANITE4_H_SMALL


def get_config(name: str) -> ArchConfig:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(REGISTRY)}") from None


def sub_quadratic(cfg: ArchConfig) -> bool:
    """Does the arch have a sub-quadratic / bounded-KV long-context path?

    SSM and hybrid archs decode with O(1)/bounded state; gemma2's sliding-
    window layers bound half its KV (global layers retained — dominant
    memory term, recorded in the roofline table).  Pure full-attention
    archs cannot hold a 500k KV usefully => long_500k is skipped for them
    (DESIGN.md §5).
    """
    if cfg.family in ("ssm", "hybrid"):
        return True
    if cfg.local_global_period and cfg.sliding_window:
        return True
    return False


def cell_applicable(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """(runs?, reason-if-skipped) for one (arch x shape) cell."""
    if shape.name == "long_500k" and not sub_quadratic(cfg):
        return False, "pure full-attention arch: no sub-quadratic path at 500k"
    return True, ""


def all_cells() -> List[Tuple[ArchConfig, ShapeConfig, bool, str]]:
    """The full 40-cell matrix with applicability flags."""
    out = []
    for cfg in ASSIGNED:
        for shape in SHAPES.values():
            ok, why = cell_applicable(cfg, shape)
            out.append((cfg, shape, ok, why))
    return out
