"""Pallas TPU flash-attention forward kernel.

Tiling: grid = (batch*heads, q_blocks, k_blocks), sequential innermost
k-block axis (TPU grids iterate sequentially, so the online-softmax state
lives in VMEM scratch across k iterations and the output tile is written on
the last one).  Q/K/V tiles are staged HBM->VMEM by BlockSpec; the MXU sees
(block_q x d) @ (d x block_k) and (block_q x block_k) @ (block_k x d)
matmuls — d and the block sizes should be multiples of 128 on real TPU
(the defaults are).

Supports causal masking, local windows (llama4-style chunked attention),
and logit softcap.  GQA is handled by the ops wrapper via a head-index map
(kv tensors are indexed at ``h // group``, never materialized repeated).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *, scale: float, causal: bool, window: int,
                      block_q: int, block_k: int, seq_k: int,
                      softcap: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
    k = k_ref[0].astype(jnp.float32)                # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    valid = k_pos < seq_k
    if causal:
        valid &= q_pos >= k_pos
    if window > 0:
        valid &= (q_pos - k_pos) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - m_safe))
    l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            m_fin = jnp.where(m_scr[...] <= NEG_INF, 0.0, m_scr[...])
            lse_ref[0] = (m_fin + jnp.log(l)).astype(lse_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 128,
                        block_k: int = 128, group: int = 1,
                        kv_len: int = 0, return_lse: bool = False,
                        interpret: bool = False):
    """q: (BH, Sq, D); k, v: (BKV, Sk, D) with BH == BKV * group.

    Returns (BH, Sq, D).  Sequences are padded to the block sizes by the
    ops wrapper; ``kv_len`` is the true (pre-padding) KV length so padded
    key columns are masked out.
    """
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    assert BH == BKV * group
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    scale = D ** -0.5

    if return_lse:
        kernel = functools.partial(
            _flash_fwd_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_k=kv_len or Sk,
            softcap=softcap)
        out_specs = [
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ]
        out_shape = [jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                     jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32)]
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
            _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, m_scr,
                              l_scr, acc_scr, scale=scale, causal=causal,
                              window=window, block_q=block_q,
                              block_k=block_k, seq_k=kv_len or Sk,
                              softcap=softcap)
        out_specs = pl.BlockSpec((1, block_q, D),
                                 lambda bh, iq, ik: (bh, iq, 0))
        out_shape = jax.ShapeDtypeStruct((BH, Sq, D), q.dtype)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),   # accumulator
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
