"""Pallas TPU paged-attention decode kernel (block-table KV read path).

Decode attention where the KV cache lives in a shared *page pool*
``(n_pages, page_size, KV, D)`` instead of a dense per-slot
``(n_slots, max_seq, KV, D)`` buffer.  Each batch row (slot) owns an
ordered row of a block table: entry ``j`` names the page holding absolute
positions ``[j*page_size, (j+1)*page_size)`` of that slot's sequence.

The block table and the per-slot decode positions ride in as
*scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``), so the
page index feeds the K/V BlockSpec index maps directly: the pages are
DMA'd HBM->VMEM exactly like contiguous KV blocks — gather by DMA
descriptor, never materialized as a contiguous copy (the pure-jnp
reference in ``ref.py`` pays that copy; the kernel does not).

Grid: ``(B, n_blocks)`` with the block axis innermost and sequential,
carrying online-softmax state (m, l, acc) in VMEM scratch across block
iterations — the same recipe as ``kernel.py``'s flash forward.  Unallocated
table entries must point at a *valid* page index (the pool uses page 0);
their keys land beyond ``pos`` and are masked.

Tiling: one program reads a whole page, every KV head of it — the block
``(1, page, KV, D)`` spans the pool's two minor dims, as the TPU tiling
asks (a one-head block would put a 1 in the second-minor dim).  The page
is flattened to ``(page*KV, D)`` rows and all ``H`` query heads score
against every row in one ``(H, D) @ (D, page*KV)`` product; a static
column map masks the rows of other KV heads.  That spends KV times the
necessary MXU work on a memory-bound step, to keep every operand tile
aligned.

**Quantized pools.**  With ``k_scales``/``v_scales`` (P, KV) float32 the
pools hold int8/fp8 values; the scales ride in as two extra VMEM side
inputs, laid out ``(P, 1, KV)`` so a page's row is a whole tile, whose
BlockSpec index map is the *same* ``tbl[b, j]`` lookup as the page DMA.
Only a query head's own KV head survives the column mask, so each score
row and each output row is dequantized by that head's scale, expanded
in-register from the page's ``(1, KV)`` row.  HBM moves the quantized
bytes, and no fp copy of the pool is ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(tables_ref, pos_ref, col_ref, q_ref, k_ref, v_ref,
                         *rest, scale: float, page_size: int, window: int,
                         softcap: float, quantized: bool):
    if quantized:
        head_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _, page, KV, D = k_ref.shape
    q = q_ref[0].astype(jnp.float32) * scale             # (H, D)
    k = k_ref[0].astype(jnp.float32).reshape(page * KV, D)
    v = v_ref[0].astype(jnp.float32).reshape(page * KV, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (H, page*KV)
    if quantized:
        # a row keeps only its own KV head's columns, so one scale per
        # row dequantizes them: (H, KV) head map x the page's (1, KV) row
        head = head_ref[...]
        s = s * jnp.sum(head * ks_ref[0], axis=1, keepdims=True)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    pos = pos_ref[b]
    col = col_ref[...]               # token offset in page; -1: other head
    k_pos = j * page_size + col
    valid = (col >= 0) & (k_pos <= pos)
    if window > 0:
        valid &= k_pos > (pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                   # (H, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - m_safe))
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (H, D)
    if quantized:
        pv = pv * jnp.sum(head * vs_ref[0], axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, block_tables, pos, *,
                       window: int = 0, softcap: float = 0.0,
                       k_scales=None, v_scales=None,
                       interpret: bool = False):
    """Single-token paged attention.

    q: (B, 1, H, D); k_pages, v_pages: (P, page, KV, D) page pools;
    block_tables: (B, nb) int32 page ids (unallocated entries must hold a
    valid page id — they are masked by position); pos: (B,) absolute
    position of the incoming token (cache entries > pos are invalid).
    With ``k_scales``/``v_scales`` (P, KV) float32 the pools hold
    quantized values, dequantized in-register (see module docstring).
    Returns (B, 1, H, D).
    """
    B, _, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    G = H // KV
    nb = block_tables.shape[1]
    quantized = k_scales is not None

    # static maps from score row r (query head r, KV head r // G) and
    # flattened page row c (token c // KV, KV head c % KV)
    row_head = np.arange(H)[:, None] // G
    c = np.arange(page * KV)[None, :]
    col = np.where(c % KV == row_head, c // KV, -1).astype(np.int32)

    kernel = functools.partial(
        _paged_decode_kernel, scale=D ** -0.5, page_size=page,
        window=window, softcap=softcap, quantized=quantized)
    page_spec = pl.BlockSpec((1, page, KV, D),
                             lambda b, j, tbl, ps: (tbl[b, j], 0, 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda b, j, tbl, ps: (0, 0))
    in_specs = [
        whole((H, page * KV)),
        pl.BlockSpec((1, H, D), lambda b, j, tbl, ps: (b, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [jnp.asarray(col), q.reshape(B, H, D), k_pages, v_pages]
    if quantized:
        # the scale side inputs reuse the page DMA's tbl[b, j] steering
        scale_spec = pl.BlockSpec((1, 1, KV),
                                  lambda b, j, tbl, ps: (tbl[b, j], 0, 0))
        head = (np.arange(KV)[None, :] == row_head).astype(np.float32)
        in_specs += [whole((H, KV)), scale_spec, scale_spec]
        operands += [jnp.asarray(head),
                     jnp.asarray(k_scales, jnp.float32).reshape(P, 1, KV),
                     jnp.asarray(v_scales, jnp.float32).reshape(P, 1, KV)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, tbl, ps: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),    # running max
            pltpu.VMEM((H, 1), jnp.float32),    # running sum
            pltpu.VMEM((H, D), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(pos, jnp.int32),
      *operands)
    return out.reshape(B, 1, H, D)
