"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``norm`` scope (``models/common.py:apply_norm``) that XLA
left outside the matmul fusions.  A fusion takes its own ``op_name``, which
is its matmul's, so the LayerNorm work fused into a matmul is read by that
matmul's scope (``attn``, ``mlp`` or ``head``) and not here."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "norm")
