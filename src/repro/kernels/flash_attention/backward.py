"""Pallas flash-attention backward kernels (dQ and dK/dV passes).

Two-pass structure (standard TPU flash-bwd):
  pass 1 (dq): grid (BH, q_blocks, k_blocks) — recompute the (bq x bk)
    probabilities from saved (o, lse), accumulate dq in VMEM scratch.
  pass 2 (dkv): grid (BH, k_blocks, q_blocks) — same recompute transposed;
    accumulate dk/dv in VMEM scratch across the (sequential) q axis.

The forward saves only (o, lse) — the flash trick: softmax probabilities
are reconstructed per tile as exp(s - lse), and dS = P * (dP - delta)
with delta = rowsum(dO * O).

Both passes skip the causally masked work as the forward does
(``kernel.causal_subtiles``): tiles above the diagonal are skipped with
their index maps held on a fetched block, tiles below it run unmasked,
and a diagonal tile computes only its sub-tiles on or below the diagonal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import (SUBTILE, causal_subtiles, count_subtiles, strip_mask,
                     strips, vmem_params)

NEG_INF = -1e30


def _mask(iq, ik, block_q, block_k, seq_k, causal, window):
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < seq_k
    if causal:
        valid &= q_pos >= k_pos
    if window > 0:
        valid &= (q_pos - k_pos) < window
    return valid


def _load(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, scale):
    """The tile's operands in float32, q scaled, and delta = rowsum(do * o):
    ``(q, k, v, do, lse, delta)``."""
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    return (q_ref[0].astype(jnp.float32) * scale,
            k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32), do,
            lse_ref[0].astype(jnp.float32),                  # (bq, 1)
            jnp.sum(do * o, axis=-1, keepdims=True))         # (bq, 1)


def _block(tile, rows, cols):
    """The part of ``_load``'s operands that the (rows, cols) block of
    scores needs."""
    q, k, v, do, lse, delta = tile
    return q[rows], k[cols], v[cols], do[rows], lse[rows], delta[rows]


def _probs_and_ds(q, k, v, do, lse, delta, valid):
    """The block's probabilities p and score gradient ds (``valid`` None:
    no mask)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse)                              # (bq, bk)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_scr, *, scale, causal, window, block_q, block_k, seq_k,
               n_k, pairs, c):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(tile, rows, cols, valid):
        q, k, v, do, lse, delta = _block(tile, rows, cols)
        _, ds = _probs_and_ds(q, k, v, do, lse, delta, valid)
        dq_scr[rows, :] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    load = lambda: _load(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, scale)
    whole = slice(None)
    if pairs is None:
        step(load(), whole, whole,
             _mask(iq, ik, block_q, block_k, seq_k, causal, window))
    else:
        @pl.when(ik < iq)
        def _below():
            step(load(), whole, whole, None)

        @pl.when(ik == iq)
        def _diagonal():
            tile = load()
            for rows, cols in strips(pairs, 0, c):
                step(tile, rows, cols, strip_mask(rows, cols))

    @pl.when(ik == n_k - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                block_q, block_k, seq_k, n_q, pairs, c):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(tile, rows, cols, valid):
        q, k, v, do, lse, delta = _block(tile, rows, cols)
        p, ds = _probs_and_ds(q, k, v, do, lse, delta, valid)
        dv_scr[cols, :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # q is pre-scaled in this kernel, so dk = ds^T @ q_scaled (no extra
        # scale factor — that would double-apply it)
        dk_scr[cols, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    load = lambda: _load(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, scale)
    whole = slice(None)
    if pairs is None:
        step(load(), whole, whole,
             _mask(iq, ik, block_q, block_k, seq_k, causal, window))
    else:
        @pl.when(iq > ik)
        def _below():
            step(load(), whole, whole, None)

        @pl.when(iq == ik)
        def _diagonal():
            tile = load()
            for rows, cols in strips(pairs, 1, c):
                step(tile, rows, cols, strip_mask(rows, cols))

    @pl.when(iq == n_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, do, lse, *, causal=True, window=0,
                        block_q=128, block_k=128, kv_len=0,
                        interpret=False, _subtile=SUBTILE):
    """q: (BH, Sq, D); k: (BH, Sk, D); v: (BH, Sk, Dv); o, do: (BH, Sq,
    Dv) (kv pre-repeated for GQA; Dv may differ from D, as in latent
    attention); lse: (BH, Sq, 1).  Returns (dq, dk, dv).  ``_subtile`` is
    for the tests only."""
    BH, Sq, D = q.shape
    _, Sk, _ = k.shape
    Dv = v.shape[-1]
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    scale = D ** -0.5
    seq_k = kv_len or Sk
    pairs, total = causal_subtiles(block_q, block_k, _subtile, causal,
                                   window, seq_k < Sk or Sq > Sk)
    count_subtiles("flash_bwd_dq", pairs, total)
    count_subtiles("flash_bwd_dkv", pairs, total)
    static = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, seq_k=seq_k,
                  pairs=pairs, c=min(_subtile, block_q))

    def kv_map(b, i, j):
        # a skipped tile above the diagonal fetches the next row's first
        # block, so its step starts no DMA of its own
        if pairs is not None:
            j = jnp.where(j <= i, j, 0)
        return b, j, 0

    q_map = lambda b, i, j: (b, i, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=nk, **static),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, Dv), kv_map),
            pl.BlockSpec((1, block_q, Dv), q_map),
            pl.BlockSpec((1, block_q, Dv), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        name="flash_bwd_dq",
        compiler_params=vmem_params(D, Dv),
        interpret=interpret,
    )(q, k, v, o, do, lse)

    def qo_map(b, j, i):
        # the skipped tiles above the diagonal come first on the q axis:
        # they hold the diagonal's block, fetched once
        if pairs is not None:
            i = jnp.maximum(i, j)
        return b, i, 0

    k_map = lambda b, j, i: (b, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=nq, **static),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), qo_map),
            pl.BlockSpec((1, block_k, D), k_map),
            pl.BlockSpec((1, block_k, Dv), k_map),
            pl.BlockSpec((1, block_q, Dv), qo_map),
            pl.BlockSpec((1, block_q, Dv), qo_map),
            pl.BlockSpec((1, block_q, 1), qo_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), k_map),
            pl.BlockSpec((1, block_k, Dv), k_map),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, Dv), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        name="flash_bwd_dkv",
        compiler_params=vmem_params(D, Dv),
        interpret=interpret,
    )(q, k, v, o, do, lse)
    return dq, dk, dv
