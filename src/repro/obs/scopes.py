"""The named scopes of the training path.

Each scope is a ``jax.named_scope``: it writes its name into the
``op_name`` metadata of every HLO instruction traced inside it, which the
profiler keeps beside each device op.  It acts only while tracing, so the
compiled program is the same with or without it.

The vocabulary follows the planner's kernel families
(:mod:`repro.core.workload`): ``embed`` and ``opt`` are its phases of
those names and ``head`` its ``loss`` phase.
"""
from __future__ import annotations

import jax

SCOPES = ("embed", "attn", "mlp", "norm", "head", "grad_accum", "opt")

#: The parts of a dropless expert layer (``models/common.py
#: apply_routed_moe``), nested inside its ``mlp`` scope: scores, bias, top-k
#: and weights; sort, permute, gather and the weighted combine; the grouped
#: matmuls; the shared expert.
MOE_SCOPES = ("router", "dispatch", "experts", "shared")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES` or
    :data:`MOE_SCOPES`; usable as a context manager or a decorator."""
    if name not in SCOPES + MOE_SCOPES:
        raise ValueError(f"unknown scope {name!r}; the vocabularies are "
                         f"{SCOPES} and {MOE_SCOPES}")
    return jax.named_scope(name)
