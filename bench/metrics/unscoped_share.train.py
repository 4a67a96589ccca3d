"""Share of the device's busy time, in %, spent in ops whose ``op_name``
carries none of the program's named scopes (``scopes.SCOPES``), over the
chips of a training cell: what the scope metrics leave unexplained."""
from bench import scopes


def read(ctx):
    return scopes.unscoped_share(ctx)
