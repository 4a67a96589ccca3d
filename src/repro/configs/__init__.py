"""Architecture registry: ``--arch <id>`` resolution.

The ten assigned architectures plus the paper's own case-study model
(``gpt3-xl``) and Moonlight-16B-A3B (``moonlight-16b-a3b``, latent attention
and dropless fine-grained experts).  IDs use the assignment spelling (dots and dashes); module
names are the sanitized forms.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from .base import MLAConfig, ModelConfig, MoEConfig, SSMConfig, \
    ShapeConfig, smoke_config, config_summary
from .shapes import SHAPES, get_shape, smoke_shape, TRAIN_4K, PREFILL_32K, \
    DECODE_32K, LONG_500K, PAPER_GPT3XL

from . import (llama3_2_3b, llama3_2_1b, nemotron_4_340b, yi_34b,
               granite_moe_1b_a400m, llama4_scout_17b_a16e,
               seamless_m4t_medium, internvl2_1b, mamba2_370m, zamba2_7b,
               gpt3_xl, moonlight_16b_a3b)

_MODULES = (llama3_2_3b, nemotron_4_340b, llama3_2_1b, yi_34b,
            granite_moe_1b_a400m, llama4_scout_17b_a16e,
            seamless_m4t_medium, internvl2_1b, mamba2_370m, zamba2_7b,
            gpt3_xl, moonlight_16b_a3b)

REGISTRY: Dict[str, ModelConfig] = {m.CONFIG.name: m.CONFIG for m in _MODULES}

# The ten assigned architectures (gpt3-xl is the paper's extra case study,
# moonlight-16b-a3b a benchmark configuration).
ASSIGNED: List[str] = [m.CONFIG.name for m in _MODULES
                       if m.CONFIG.name not in ("gpt3-xl",
                                                "moonlight-16b-a3b")]

# Canonical assigned shapes (paper_gpt3xl is extra).
ASSIGNED_SHAPES: List[str] = ["train_4k", "prefill_32k", "decode_32k",
                              "long_500k"]


def get_config(arch: str) -> ModelConfig:
    try:
        return REGISTRY[arch]
    except KeyError:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(REGISTRY)}")


def cell_is_runnable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch x shape) cell runs, and if not, why (DESIGN.md skips).

    ``long_500k`` needs sub-quadratic attention: it runs for SSM/hybrid archs
    and for chunked-local-attention archs (llama4-scout); it is skipped for
    pure full-attention archs.
    """
    if shape.name.startswith("long_") and not cfg.subquadratic:
        return False, (f"{cfg.name} is pure full-attention (O(S^2)); "
                       f"{shape.name} requires sub-quadratic attention")
    return True, ""


def all_cells(include_skipped: bool = False):
    """Yield (arch_id, shape_name, runnable, reason) for the 40-cell grid."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for sname in ASSIGNED_SHAPES:
            ok, why = cell_is_runnable(cfg, get_shape(sname))
            if ok or include_skipped:
                yield arch, sname, ok, why


__all__ = [
    "MLAConfig", "ModelConfig", "MoEConfig", "SSMConfig", "ShapeConfig",
    "REGISTRY", "ASSIGNED", "ASSIGNED_SHAPES", "SHAPES",
    "get_config", "get_shape", "smoke_config", "smoke_shape",
    "cell_is_runnable", "all_cells", "config_summary",
    "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K", "PAPER_GPT3XL",
]
