"""Jit-able public wrapper for the flash-attention kernel.

Handles layout ((B, S, H, D) model layout -> (B*H, S, D) kernel layout),
GQA head mapping, and padding to block multiples.  ``interpret`` runs the
kernel through the Pallas interpreter, which the CPU tests use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_fwd


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    group = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    o = flash_attention_fwd(qf, kf, vf, causal=causal, window=window,
                            softcap=softcap, block_q=bq, block_k=bk,
                            group=group, kv_len=Sk, interpret=interpret)
    if pq:
        o = o[:, :Sq]
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Differentiable (training) variant: Pallas forward + Pallas backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_train(q, k, v, causal: bool = True, window: int = 0,
                          block_q: int = 128, block_k: int = 128,
                          interpret: bool = False):
    """Differentiable flash attention (no softcap; GQA via kv repeat).

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  The backward pass recomputes
    tile probabilities from the saved (o, lse) — the flash-bwd recipe.
    """
    o, _ = _fa_train_fwd(q, k, v, causal, window, block_q, block_k,
                         interpret)
    return o


def _fa_layout(q, k, v):
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    return qf, kf, vf, (B, Sq, Sk, H, KV, D, G)


def _fa_train_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    from .kernel import flash_attention_fwd
    qf, kf, vf, dims = _fa_layout(q, k, v)
    B, Sq, Sk, H, KV, D, G = dims
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    qp = jnp.pad(qf, ((0, 0), (0, pq), (0, 0))) if pq else qf
    kp = jnp.pad(kf, ((0, 0), (0, pk), (0, 0))) if pk else kf
    vp = jnp.pad(vf, ((0, 0), (0, pk), (0, 0))) if pk else vf
    o, lse = flash_attention_fwd(qp, kp, vp, causal=causal, window=window,
                                 block_q=bq, block_k=bk, group=1,
                                 kv_len=Sk, return_lse=True,
                                 interpret=interpret)
    res = (qp, kp, vp, o, lse, dims)
    out = o[:, :Sq] if pq else o
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3), res


def _fa_train_bwd(causal, window, block_q, block_k, interpret, res, g):
    from .backward import flash_attention_bwd
    qp, kp, vp, o, lse, dims = res
    B, Sq, Sk, H, KV, D, G = dims
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq = (-Sq) % bq
    gf = g.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    gp = jnp.pad(gf, ((0, 0), (0, pq), (0, 0))) if pq else gf
    dq, dk, dv = flash_attention_bwd(
        qp, kp, vp, o, gp, lse, causal=causal, window=window,
        block_q=bq, block_k=bk, kv_len=Sk, interpret=interpret)
    dq = dq[:, :Sq].reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    dk = dk[:, :Sk].reshape(B, H, Sk, D).transpose(0, 2, 1, 3)
    dv = dv[:, :Sk].reshape(B, H, Sk, D).transpose(0, 2, 1, 3)
    if G > 1:  # sum gradients over the repeated query groups
        dk = dk.reshape(B, Sk, KV, G, D).sum(axis=3)
        dv = dv.reshape(B, Sk, KV, G, D).sum(axis=3)
    return dq, dk, dv


flash_attention_train.defvjp(_fa_train_fwd, _fa_train_bwd)
