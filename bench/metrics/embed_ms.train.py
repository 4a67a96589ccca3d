"""Device time, in ms per traced step and the mean over chips, of the ops
in the program's ``embed`` scope (``models/common.py:embed_tokens``): the
token and position lookups and, in the backward pass, the scatter of their
gradients into the tables."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "embed")
