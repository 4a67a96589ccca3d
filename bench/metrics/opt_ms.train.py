"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``opt`` scope (``train/step.py:train_step``, around
``adamw_update``): the gradient norm, the clipping and the AdamW update."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "opt")
