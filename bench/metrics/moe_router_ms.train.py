"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``router`` sub-scope of an expert layer
(``models/common.py:apply_routed_moe``, inside ``mlp``): the router:
float32 scores over all experts, the selection bias, top-k and the
weights, and the loads and balance loss. None for a program without
expert layers."""
from bench import moe_scopes


def read(ctx):
    return moe_scopes.step_ms(ctx, "router")
