"""Share of its roofline reached by the flash-attention backward, the dq
and dk/dv kernels together (``kernels/flash_bwd.py``), from the trace."""
from bench import harness as H


def read(ctx):
    return H.kernel_roofline(ctx, "flash_bwd")
