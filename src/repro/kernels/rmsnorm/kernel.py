"""Pallas fused RMSNorm (+scale) kernel.

Row-tiled: grid over row blocks, each block (block_rows x d) resident in
VMEM; the f32 mean-square reduction and the scale multiply fuse into one
HBM round-trip (the paper's layernorm-class kernels are exactly this
memory-bound shape — Table 1 rows #1/#18, ~30% energy headroom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)              # (br, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_rows(x, w, *, eps: float = 1e-5, block_rows: int = 256,
                 interpret: bool = False):
    """x: (rows, d); w: (d,)."""
    rows, d = x.shape
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x, w)
