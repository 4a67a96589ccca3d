"""Share, in %, of the expert layers' grouped-matmul calls in the traced
steps that ran over the buffer of all T x K token copies (the tokens of a
microbatch times top-k), not over the capped buffer of the rows routed to
the held experts (``models/common.py:routed_cap``).  The calls are the
megablox ``gmm``/``tgmm`` kernels as ``kernels/moe_experts.py`` recognises
them, and a call's rows are those of its 2-D arrays.  0 where every layer
ran capped; 100 for a program with no cap, whose buffer always holds every
copy.  None for a model without routed experts or where no such call
ran."""
import re

from bench import harness as H

_ROWS = re.compile(r"(?:bf16|f32)\[(\d+),\d+\]")


def read(ctx):
    moe = ctx.config.get("model", {}).get("moe", {})
    if not moe.get("experts_held"):
        return None
    k = H.kernel("moe_experts")
    tokens = (ctx.traffic["global_batch"] * ctx.traffic["seq_len"]
              // ctx.config["train"]["accum_steps"])
    full = tokens * moe["top_k"]
    calls = over = 0
    for dev in ctx.trace.devices:
        for op in ctx.trace.ops(dev):
            if k.call_cost(op, 1.0) is None:
                continue
            rows = [int(m.group(1)) for m in map(
                _ROWS.fullmatch, op.operand_shapes + [op.shape]) if m]
            calls += 1
            over += rows[0] == full
    return 100.0 * over / calls if calls else None
