"""Causal flash-attention forward at latent attention's widths
(``kernels/flash_attention/kernel.py`` with values narrower than queries
and keys: Moonlight's q/k of 192 and v of 128).

One call computes, for ``BH`` (batch x head) rows of ``S`` queries and
keys, the outputs and the log-sum-exp of each query.  The algorithm needs
q.k (``Dqk`` wide) and p.v (``Dv`` wide) over the ``S (S + 1) / 2`` causal
pairs, two operations a product, and reads q and k (``Dqk``) and v and
writes o (``Dv``) once in bf16, plus the float32 log-sum-exp.

In the trace it is a ``tpu_custom_call`` of three bf16 operands ``(BH, S,
Dqk)``, ``(BH, S, Dqk)``, ``(BH, S, Dv)`` with ``Dqk != Dv``, whose result
is ``(bf16[BH,S,Dv], f32[BH,S,1])``.  Calls with one width for all three
are ``flash_fwd.py``'s.
"""
import re

_ARRAY = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
_RESULT = re.compile(r"\(bf16\[(\d+),(\d+),(\d+)\], f32\[\1,\2,1\]\)")


def cost(BH: int, S: int, Dqk: int, Dv: int, itemsize: int = 2):
    """(operations, bytes) one call needs."""
    pairs = S * (S + 1) / 2
    return (2.0 * BH * (Dqk + Dv) * pairs,
            float(itemsize * 2 * BH * S * (Dqk + Dv) + 4 * BH * S))


def call_cost(op):
    """(operations, bytes) of ``op`` when it is a call of this kernel,
    else None."""
    ops = op.operand_shapes
    if op.custom_call_target != "tpu_custom_call" or len(ops) != 3:
        return None
    shapes = [_ARRAY.fullmatch(s) for s in ops]
    res = _RESULT.fullmatch(op.shape)
    if not res or not all(shapes):
        return None
    (BH, S, Dqk), _, (_, _, Dv) = [tuple(int(x) for x in m.groups())
                                   for m in shapes]
    if Dqk == Dv or int(res.group(3)) != Dv:
        return None
    return cost(BH, S, Dqk, Dv)
