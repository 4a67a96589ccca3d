"""Model FLOP/s utilization of a training cell of a model with latent
attention and routed experts, in %: the operations a token needs
(``moe_flops.flops_per_token``: routed experts at top-k x held / n_experts
a token) times the tokens per second of the run's untraced steps, over the
chips' bf16 peak (``peaks.json``).  None for a model without both."""
from bench import moe_flops


def read(ctx):
    m, c = ctx.config.get("model", {}), ctx.counts
    if "mla" not in m or "moe" not in m or not c.get("steps"):
        return None
    rate = c["steps"] * c["tokens_per_step"] / c["seconds"]
    return 100.0 * rate * moe_flops.flops_per_token(
        m, ctx.traffic["seq_len"]) / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
