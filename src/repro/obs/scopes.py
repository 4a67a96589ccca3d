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


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`; usable as a
    context manager or a decorator."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; the vocabulary is "
                         f"{SCOPES}")
    return jax.named_scope(name)
