"""Pallas flash-attention backward kernels (dQ and dK/dV passes).

Two-pass structure (standard TPU flash-bwd):
  pass 1 (dq): grid (BH, q_blocks, k_blocks) — recompute the (bq x bk)
    probabilities from saved (o, lse), accumulate dq in VMEM scratch.
  pass 2 (dkv): grid (BH, k_blocks, q_blocks) — same recompute transposed;
    accumulate dk/dv in VMEM scratch across the (sequential) q axis.

The forward saves only (o, lse) — the flash trick: softmax probabilities
are reconstructed per tile as exp(s - lse), and dS = P * (dP - delta)
with delta = rowsum(dO * O).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_scr, *, scale, causal, window, block_q, block_k, seq_k,
               n_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    lse = lse_ref[0].astype(jnp.float32)              # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    valid = _mask(iq, ik, block_q, block_k, seq_k, causal, window)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)       # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)   # (bq, 1)
    ds = p * (dp - delta)
    dq_scr[...] += scale * jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                block_q, block_k, seq_k, n_q):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    lse = lse_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    valid = _mask(iq, ik, block_q, block_k, seq_k, causal, window)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    ds = p * (dp - delta)
    # q is pre-scaled in this kernel, so dk = ds^T @ q_scaled (no extra
    # scale factor — that would double-apply it)
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iq == n_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, do, lse, *, causal=True, window=0,
                        block_q=128, block_k=128, kv_len=0,
                        interpret=False):
    """q,o,do: (BH, Sq, D); k,v: (BH, Sk, D) (kv pre-repeated for GQA);
    lse: (BH, Sq, 1).  Returns (dq, dk, dv)."""
    BH, Sq, D = q.shape
    _, Sk, _ = k.shape
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    scale = D ** -0.5
    seq_k = kv_len or Sk

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_k=seq_k, n_k=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, o, do, lse)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_k=seq_k, n_q=nq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, o, do, lse)
    return dq, dk, dv
