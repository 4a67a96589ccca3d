"""Pallas TPU flash-attention forward kernel.

Tiling: grid = (batch*heads, q_blocks, k_blocks), sequential innermost
k-block axis (TPU grids iterate sequentially, so the online-softmax state
lives in VMEM scratch across k iterations and the output tile is written on
the last one).  Q/K/V tiles are staged HBM->VMEM by BlockSpec; the MXU sees
(block_q x d) @ (d x block_k) and (block_q x block_k) @ (block_k x d)
matmuls — d and the block sizes should be multiples of 128 on real TPU
(the defaults are).

Causal work skipping: in a causal call with square blocks, no window and
no padded keys (:func:`causal_subtiles`), tiles wholly above the diagonal
are skipped (their K/V index maps stay on a block already fetched, so no
DMA starts), tiles wholly below it run unmasked, and a tile on the
diagonal is cut into sub-tiles of edge :data:`SUBTILE` of which only those
on or below the diagonal are computed, a row of them at a time under the
mask.  The diagonal tile is visited first (:func:`diagonal_first`): it
starts every row's softmax state, which costs less than merging into one.
Every other call computes each tile whole under the mask.

Supports causal masking, local windows (llama4-style chunked attention),
and logit softcap.  GQA is handled by the ops wrapper via a head-index map
(kv tensors are indexed at ``h // group``, never materialized repeated).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.metrics import MetricsRegistry

NEG_INF = -1e30

#: Edge of the sub-tiles a diagonal tile is cut into (capped at the
#: block).  On a TPU v5e at seq 1024, blocks of 1024, D 128, 256 gave the
#: least forward-plus-backward time: 128 pays more per strip, 512
#: computes more of the masked square.
SUBTILE = 256

#: Sub-tiles of one diagonal tile, computed and in all, added up per
#: kernel name each time a kernel is traced (no device cost).
SUBTILE_COUNTS = MetricsRegistry()


def vmem_params(*widths: int):
    """Compiler parameters of a flash kernel over heads of ``widths``.
    Heads wider than 128 (latent attention's q/k of 192) at blocks of 1024
    outgrow the default 16 MiB of scoped VMEM; a TPU v5e core has 128 MiB,
    so such kernels may use 48 MiB.  Narrower heads keep the default."""
    if max(widths) <= 128:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=48 << 20)


def causal_subtiles(block_q: int, block_k: int, c: int, causal: bool,
                    window: int, ragged: bool = False):
    """``(pairs, total)``: the (row, col) sub-tiles of edge ``min(c,
    block_q)`` that a tile on the diagonal computes, in row order, and how
    many sub-tiles the tile holds.

    Only a causal call with square blocks, no window and keys that line up
    with the queries tile for tile has the same structure in every tile,
    known while tracing.  ``ragged`` says they do not: padded keys, or
    more query tiles than key tiles.  Any other call gets
    ``pairs`` None: each tile is computed whole, under the mask, and all
    ``total`` sub-tiles count as computed.
    """
    c = min(c, block_q)
    if block_q % c:
        return None, 1
    n = block_q // c
    if not causal or window > 0 or block_q != block_k or ragged:
        return None, n * n
    return tuple((i, j) for i in range(n) for j in range(i + 1)), n * n


def count_subtiles(kernel: str, pairs, total: int) -> None:
    """Add one lowering of ``kernel`` to :data:`SUBTILE_COUNTS`."""
    SUBTILE_COUNTS.counter("flash_subtiles_computed", kernel=kernel).inc(
        total if pairs is None else len(pairs))
    SUBTILE_COUNTS.counter("flash_subtiles_total", kernel=kernel).inc(total)


def strips(pairs, axis: int, c: int):
    """The sub-tiles ``pairs`` grouped by their row (``axis`` 0) or column
    (1) into strips of the tile: ``[(rows, cols), ...]`` as slices.  A
    row's (column's) sub-tiles are contiguous, so each strip is computed
    as one block."""
    groups = {}
    for p in pairs:
        groups.setdefault(p[axis], []).append(p[1 - axis])
    out = []
    for i, js in sorted(groups.items()):
        assert js == list(range(js[0], js[-1] + 1)), js
        own = slice(i * c, (i + 1) * c)
        other = slice(js[0] * c, (js[-1] + 1) * c)
        out.append((own, other) if axis == 0 else (other, own))
    return out


def strip_mask(rows: slice, cols: slice):
    """Causal mask of the (rows, cols) block of a tile on the diagonal."""
    shape = (rows.stop - rows.start, cols.stop - cols.start)
    return (rows.start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            >= cols.start + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _softmax_step(s, valid, v, m_prev, l_prev, acc_prev, softcap):
    """One online-softmax step over scores ``s`` (rows x cols) and values
    ``v``; ``valid`` None means every pair counts."""
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - m_safe))
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * corr + pv


def _softmax_first(s, valid, v, softcap):
    """The softmax state of rows met for the first time, each of which
    holds a valid pair: masked scores sit at ``NEG_INF`` below a finite
    row max, so their exp is exactly 0."""
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m, jnp.sum(p, axis=1, keepdims=True), pv


def diagonal_first(iq, t, nk):
    """The k block that step ``t`` of q row ``iq`` visits when the causal
    work is skipped: the diagonal block first, so that it starts the
    softmax state of every row, then the blocks below the diagonal in
    order.  The skipped steps after them hold the next row's diagonal
    block, so they start no DMA of their own."""
    return jnp.where(t == 0, iq, jnp.where(
        t <= iq, t - 1, jnp.minimum(iq + 1, nk - 1)))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *, scale: float, causal: bool, window: int,
                      block_q: int, block_k: int, seq_k: int,
                      softcap: float, pairs, c: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)   # the k block, or the step with ``pairs``
    nk = pl.num_programs(2)

    def load():
        return (q_ref[0].astype(jnp.float32) * scale,     # (bq, d)
                k_ref[0].astype(jnp.float32),             # (bk, d)
                v_ref[0].astype(jnp.float32))

    def step(q, k, v, valid):
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m, l, acc = _softmax_step(s, valid, v, m_scr[...], l_scr[...],
                                  acc_scr[...], softcap)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    if pairs is None:
        @pl.when(ik == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_k
        if causal:
            valid &= q_pos >= k_pos
        if window > 0:
            valid &= (q_pos - k_pos) < window
        step(*load(), valid)
    else:
        @pl.when(ik == 0)
        def _diagonal():
            q, k, v = load()
            for rows, cols in strips(pairs, 0, c):
                m, l, acc = _softmax_first(
                    jax.lax.dot_general(q[rows], k[cols],
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32),
                    strip_mask(rows, cols), v[cols], softcap)
                m_scr[rows, :] = m
                l_scr[rows, :] = l
                acc_scr[rows, :] = acc

        @pl.when((ik > 0) & (ik <= iq))
        def _below():
            step(*load(), None)

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
                        interpret: bool = False, _subtile: int = SUBTILE):
    """q, k: (BH, Sq, D), (BKV, Sk, D); v: (BKV, Sk, Dv) with BH == BKV *
    group.  Values may be narrower than queries and keys (latent attention:
    D 192, Dv 128); scores are scaled by ``D ** -0.5``.

    Returns (BH, Sq, Dv).  Sequences are padded to the block sizes by the
    ops wrapper; ``kv_len`` is the true (pre-padding) KV length so padded
    key columns are masked out.  ``_subtile`` is for the tests only.
    """
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    Dv = v.shape[-1]
    assert BH == BKV * group
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    scale = D ** -0.5
    seq_k = kv_len or Sk
    pairs, total = causal_subtiles(block_q, block_k, _subtile, causal,
                                   window, seq_k < Sk or Sq > Sk)
    count_subtiles("flash_fwd", pairs, total)
    body = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_k=seq_k, softcap=softcap,
        pairs=pairs, c=min(_subtile, block_q))

    if return_lse:
        kernel = body
        out_specs = [
            pl.BlockSpec((1, block_q, Dv), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ]
        out_shape = [jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
                     jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32)]
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, *scratch):
            body(q_ref, k_ref, v_ref, o_ref, None, *scratch)
        out_specs = pl.BlockSpec((1, block_q, Dv),
                                 lambda bh, iq, ik: (bh, iq, 0))
        out_shape = jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype)

    def kv_map(bh, iq, ik):
        if pairs is not None:
            ik = diagonal_first(iq, ik, nk)
        return bh // group, ik, 0

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, Dv), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, Dv), jnp.float32),  # accumulator
        ],
        name="flash_fwd",
        compiler_params=vmem_params(D, Dv),
        interpret=interpret,
    )(q, k, v)
