"""Share of its roofline reached by the flash-attention backward at latent
attention's widths, the dq and dk/dv kernels together
(``kernels/mla_flash_bwd.py``), from the trace."""
from bench import harness as H


def read(ctx):
    return H.kernel_roofline(ctx, "mla_flash_bwd")
