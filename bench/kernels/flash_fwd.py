"""Causal flash-attention forward (``kernels/flash_attention/kernel.py``).

One call computes, for ``BH`` (batch x head) rows of ``S`` queries and
``S`` keys of width ``D``, the outputs and the log-sum-exp of each query.
The algorithm needs q.k and p.v over the ``S (S + 1) / 2`` causal pairs,
two operations a product, and reads q, k, v and writes o once (bf16) plus
the float32 log-sum-exp.

In the trace it is a ``tpu_custom_call`` of three bf16 ``(BH, S, D)``
operands whose result is ``(bf16[BH,S,D], f32[BH,S,1])``; the instruction
name (``closed_call.N``, ``rematted_computation.N``) is not stable.
"""
import re

_RESULT = re.compile(r"\(bf16\[(\d+),(\d+),(\d+)\], f32\[\1,\2,1\]\)")


def cost(BH: int, S: int, D: int, itemsize: int = 2):
    """(operations, bytes) one call needs."""
    pairs = S * (S + 1) / 2
    return 4.0 * BH * D * pairs, float(itemsize * 4 * BH * S * D + 4 * BH * S)


def call_cost(op):
    """(operations, bytes) of ``op`` when it is a call of this kernel,
    else None."""
    if op.custom_call_target != "tpu_custom_call" or \
            len(op.operand_shapes) != 3:
        return None
    m = _RESULT.fullmatch(op.shape)
    return cost(*(int(x) for x in m.groups())) if m else None
