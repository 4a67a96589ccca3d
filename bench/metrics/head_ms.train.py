"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``head`` scope (``models/common.py:unembed`` and the loss in
``models/transformer.py:loss``): the logits and the cross-entropy, and in
the backward pass the head's weight gradient and its input gradient, into
which XLA fuses the final LayerNorm's backward."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "head")
