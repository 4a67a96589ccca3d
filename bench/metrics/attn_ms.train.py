"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``attn`` scope (``models/common.py:attention_block``):
the q, k, v and output projections and the flash kernels, forward,
recomputed and backward."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "attn")
