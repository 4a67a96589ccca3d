"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``grad_accum`` scope (``train/step.py:train_step``): the
float32 gradient accumulator's zeros and its sum over the microbatches."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "grad_accum")
