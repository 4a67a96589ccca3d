"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``experts`` sub-scope of an expert layer
(``models/common.py:apply_routed_moe``, inside ``mlp``): the held
experts' grouped matmuls (the megablox ``gmm``/``tgmm`` kernels),
forward and backward. None for a program without expert layers."""
from bench import moe_scopes


def read(ctx):
    return moe_scopes.step_ms(ctx, "experts")
