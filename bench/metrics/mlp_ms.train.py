"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``mlp`` scope (``models/common.py:apply_mlp`` and
``apply_moe``), forward, recomputed and backward."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "mlp")
