"""Pallas Mamba2 SSD chunked-scan kernel.

Grid = (batch, heads, chunks) with the chunk axis innermost (sequential on
TPU), carrying the (N x P) SSM state in VMEM scratch across chunks — the
inter-chunk recurrence never leaves VMEM.  Each step computes the
intra-chunk dual form (two (Q x Q)-tiled MXU matmuls) plus the state
update, i.e. the SSD algorithm of arXiv:2405.21060 restructured for the
TPU memory hierarchy: HBM traffic is exactly one read of x/a/B/C and one
write of y per token.

Block shapes: x (1,Q,1,P), a (1,Q,1), B/C (1,Q,1,N); Q (chunk) and P/N
should be multiples of the 128-lane register tiling on real TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, h_final_ref, h_scr, *,
                chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, :, 0].astype(jnp.float32)       # (Q, P)
    a = a_ref[0, :, 0].astype(jnp.float32)       # (Q,)
    Bm = b_ref[0, :, 0].astype(jnp.float32)      # (Q, N)
    Cm = c_ref[0, :, 0].astype(jnp.float32)      # (Q, N)

    a_cs = jnp.cumsum(a)                          # inclusive (Q,)
    a_tot = a_cs[-1]

    # intra-chunk dual form: L[q,k] = exp(a_cs[q]-a_cs[k]) for q >= k
    seg = a_cs[:, None] - a_cs[None, :]
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y_intra = jax.lax.dot_general(CB * L, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    h = h_scr[...]                                # (N, P)
    decay_in = jnp.exp(a_cs)[:, None]             # (Q, 1)
    y_off = jax.lax.dot_general(Cm * decay_in, h,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, :, 0] = (y_intra + y_off).astype(y_ref.dtype)

    # state update: h <- exp(a_tot) h + sum_k exp(a_tot - a_cs[k]) B_k x_k^T
    decay_out = jnp.exp(a_tot - a_cs)[:, None]    # (Q, 1)
    s_c = jax.lax.dot_general(Bm * decay_out, x, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (N, P)
    h_scr[...] = jnp.exp(a_tot) * h + s_c

    @pl.when(ic == nc - 1)
    def _emit_state():
        h_final_ref[0, 0] = h_scr[...].astype(h_final_ref.dtype)


def ssd_scan(x, a, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """x: (B,S,H,P); a: (B,S,H); Bm, Cm: (B,S,H,N) (already head-mapped).

    Returns (y: (B,S,H,P), h_final: (B,H,N,P)).  S must be padded to a
    multiple of ``chunk`` by the ops wrapper.
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        name="ssd_scan",
        interpret=interpret,
    )(x, a, Bm, Cm)
    return y, h_final
