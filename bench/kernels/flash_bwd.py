"""Causal flash-attention backward (``kernels/flash_attention/backward.py``).

One backward pass over ``BH`` rows of ``S`` queries and keys of width
``D`` needs five products over the causal pairs (the scores q.k again,
dp = do.v, dv = p.do, dq = ds.k, dk = ds.q), two operations a product;
it reads q, k, v, o, do and the log-sum-exp and writes dq, dk, dv.

The program splits it into two kernels, both ``tpu_custom_call`` with the
six operands (q, k, v, o, do: bf16 ``(BH, S, D)``; lse: f32
``(BH, S, 1)``): the dq kernel returns ``bf16[BH,S,D]`` and carries the
whole pass's need; the dk/dv kernel returns the pair and carries none, so
its time counts against the same need.
"""
import re

_ONE = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
_PAIR = re.compile(r"\(bf16\[(\d+),(\d+),(\d+)\], bf16\[\1,\2,\3\]\)")


def cost(BH: int, S: int, D: int, itemsize: int = 2):
    """(operations, bytes) one backward pass needs."""
    pairs = S * (S + 1) / 2
    return (10.0 * BH * D * pairs,
            float(itemsize * 8 * BH * S * D + 4 * BH * S))


def call_cost(op):
    """(operations, bytes) charged to ``op`` when it is one of the two
    kernels, else None."""
    ops = op.operand_shapes
    if op.custom_call_target != "tpu_custom_call" or len(ops) != 6 or \
            not ops[-1].startswith("f32"):
        return None
    m = _ONE.fullmatch(op.shape)
    if m:
        return cost(*(int(x) for x in m.groups()))
    return (0.0, 0.0) if _PAIR.fullmatch(op.shape) else None
