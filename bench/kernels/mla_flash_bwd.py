"""Causal flash-attention backward at latent attention's widths
(``kernels/flash_attention/backward.py`` with values narrower than queries
and keys: Moonlight's q/k of 192 and v of 128).

One backward pass over ``BH`` rows of ``S`` queries and keys needs five
products over the causal pairs, two operations a product: the scores q.k
again, dq = ds.k and dk = ds.q (``Dqk`` wide), dp = do.v and dv = p.do
(``Dv`` wide).  It reads q, k, v, o, do and the log-sum-exp and writes
dq, dk, dv.

The program splits it into two kernels, both ``tpu_custom_call`` with six
operands (q, k: bf16 ``(BH, S, Dqk)``; v, o, do: bf16 ``(BH, S, Dv)``;
lse: f32 ``(BH, S, 1)``), ``Dqk != Dv``: the dq kernel returns
``bf16[BH,S,Dqk]`` and carries the whole pass's need; the dk/dv kernel
returns ``(bf16[BH,S,Dqk], bf16[BH,S,Dv])`` and carries none, so its time
counts against the same need.
"""
import re

_ARRAY = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
_PAIR = re.compile(r"\(bf16\[(\d+),(\d+),(\d+)\], bf16\[\1,\2,(\d+)\]\)")


def cost(BH: int, S: int, Dqk: int, Dv: int, itemsize: int = 2):
    """(operations, bytes) one backward pass needs."""
    pairs = S * (S + 1) / 2
    return (2.0 * BH * (3 * Dqk + 2 * Dv) * pairs,
            float(itemsize * 4 * BH * S * (Dqk + Dv) + 4 * BH * S))


def call_cost(op):
    """(operations, bytes) charged to ``op`` when it is one of the two
    kernels, else None."""
    ops = op.operand_shapes
    if op.custom_call_target != "tpu_custom_call" or len(ops) != 6 or \
            not ops[-1].startswith("f32"):
        return None
    shapes = [_ARRAY.fullmatch(s) for s in ops[:5]]
    if not all(shapes):
        return None
    dims = [tuple(int(x) for x in m.groups()) for m in shapes]
    BH, S, Dqk = dims[0]
    Dv = dims[2][2]
    if Dqk == Dv or dims[1][2] != Dqk or dims[3][2] != Dv:
        return None
    if op.shape == f"bf16[{BH},{S},{Dqk}]":
        return cost(BH, S, Dqk, Dv)
    pair = _PAIR.fullmatch(op.shape)
    return (0.0, 0.0) if pair and int(pair.group(4)) == Dv else None
