"""Share of its roofline reached by the flash-attention forward kernel at
latent attention's widths, q/k wider than v (``kernels/mla_flash_fwd.py``),
from the device trace; the forward runs again inside the backward under
rematerialization, and every run counts."""
from bench import harness as H


def read(ctx):
    return H.kernel_roofline(ctx, "mla_flash_fwd")
