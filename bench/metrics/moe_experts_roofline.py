"""Share of their roofline reached by the grouped matmuls of the expert
layers (``kernels/moe_experts.py``: the megablox ``gmm`` and ``tgmm``
kernels), forward and backward, from the trace.
The need is counted at the EXPECTED routed rows, tokens x top-k x held /
n_experts (uniform routing), not at the rows that ran, which only the
device knows: routing that loads the held experts above that expectation
reads high, below it low.  None where no such kernel ran."""
from bench import harness as H


def read(ctx):
    m = ctx.config.get("model", {})
    e = m.get("moe", {})
    if not e.get("experts_held"):
        return None
    k = H.kernel("moe_experts")
    share = e["experts_held"] / e["n_experts"]
    flops, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    shares = []
    for dev in ctx.trace.devices:
        need = took = 0.0
        for op in ctx.trace.ops(dev):
            c = k.call_cost(op, share)
            if c is not None:
                need += max(c[0] / flops, c[1] / bw)
                took += op.duration * 1e-9
        if took:
            shares.append(need / took)
    return 100.0 * sum(shares) / len(shares) if shares else None
