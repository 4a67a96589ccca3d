"""The grouped matmuls of a dropless expert layer (``models/common.py
grouped_matmul``: on a TPU the Pallas megablox kernels ``gmm`` and, for
the weight gradient, ``tgmm``).

Each holds a 3-D array of the experts held here ``(G, p, q)`` (weights, or
the weight gradient it returns) and 2-D arrays of ``M`` rows, the
token-expert assignments sorted by expert (``M`` is tokens x top-k, the
buffer that holds every assignment, dropless).  Only the rows of the
held experts are computed, and how many they are is known only on the
device.  So the need is counted at the expected routed rows ``R = M *
held / n_experts`` (uniform routing), not at the rows that ran: 2 R p q
operations, and the bytes of R rows of each 2-D array plus the whole 3-D
one.  A kernel is recognised by its arrays alone: a ``tpu_custom_call``
with one 3-D and two 2-D floating arrays of one row count (the integer
group metadata aside), which no other kernel of the training path has.
"""
import re

_ARRAY = re.compile(r"(bf16|f32)\[([\d,]+)\]")


def call_cost(op, held_share: float):
    """(operations, bytes) of ``op`` when it is an expert grouped matmul,
    counted at ``held_share`` of its rows, else None."""
    if op.custom_call_target != "tpu_custom_call":
        return None
    arrays = [_ARRAY.fullmatch(s) for s in op.operand_shapes + [op.shape]]
    arrays = [(m.group(1), [int(x) for x in m.group(2).split(",")])
              for m in arrays if m]
    three = [a for a in arrays if len(a[1]) == 3]
    two = [a for a in arrays if len(a[1]) == 2]
    if len(three) != 1 or len(two) != 2 or two[0][1][0] != two[1][1][0]:
        return None
    _, (G, p, q) = three[0]
    size = {"bf16": 2, "f32": 4}
    rows = two[0][1][0] * held_share
    nbytes = sum(rows * dims[1] * size[dt] for dt, dims in two) + \
        G * p * q * size[three[0][0]]
    return 2.0 * rows * p * q, float(nbytes)
