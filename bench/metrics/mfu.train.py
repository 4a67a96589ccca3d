"""Model FLOP/s utilization of a training cell, in %: the operations the
forward and backward passes need per token (``train.model_flops_per_token``)
times the tokens per second of the run's untraced steps, over the chips'
bf16 peak (``peaks.json``)."""


def read(ctx):
    c = ctx.counts
    if not c.get("steps"):
        return None
    rate = c["steps"] * c["tokens_per_step"] / c["seconds"]
    return 100.0 * rate * c["model_flops_per_token"] / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
