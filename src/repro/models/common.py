"""Shared model-building blocks (pure JAX, functional).

Parameters are plain nested dicts of ``jnp.ndarray``.  Every parameter is
declared through a :class:`ParamBuilder`, which can run in three modes:

* ``init``     — materialize arrays (smoke tests, real training),
* ``abstract`` — produce ``jax.ShapeDtypeStruct`` (dry-run, no allocation),
* ``axes``     — produce logical-axis tuples (sharding-rule application).

This guarantees the param tree, its abstract twin, and its sharding spec can
never drift apart: they are generated by the same declaration code.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.flash_attention import FLASH_RESIDUALS
from ..obs.scopes import scope

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Param builder
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Declares parameters once; materializes arrays / specs / axes."""

    INIT, ABSTRACT, AXES = "init", "abstract", "axes"

    def __init__(self, mode: str, rng: Optional[jax.Array] = None,
                 dtype=jnp.float32):
        assert mode in (self.INIT, self.ABSTRACT, self.AXES)
        self.mode = mode
        self.rng = rng
        self.dtype = dtype
        self._counter = 0

    def _next_key(self):
        self._counter += 1
        return jax.random.fold_in(self.rng, self._counter)

    def param(self, shape: Sequence[int], axes: Tuple[Optional[str], ...],
              scale: Optional[float] = None, init: str = "normal"):
        """Declare one parameter.

        ``axes`` names each dim logically (e.g. ``("embed", "heads",
        "head_dim")``); ``None`` marks a never-sharded dim.
        """
        shape = tuple(int(s) for s in shape)
        assert len(shape) == len(axes), (shape, axes)
        if self.mode == self.AXES:
            return axes
        if self.mode == self.ABSTRACT:
            return jax.ShapeDtypeStruct(shape, self.dtype)
        key = self._next_key()
        if init == "zeros":
            return jnp.zeros(shape, self.dtype)
        if init == "ones":
            return jnp.ones(shape, self.dtype)
        if scale is None:
            # fan-in scaled normal (truncated-normal-ish via plain normal)
            fan_in = shape[0] if len(shape) > 1 else max(shape[0], 1)
            scale = fan_in ** -0.5
        return (jax.random.normal(key, shape) * scale).astype(self.dtype)


def remat(fn):
    """``fn`` under ``jax.checkpoint``, the rule of every layer stack: the
    backward recomputes the layer from its input but keeps the training
    flash kernel's output and log-sum-exp (``FLASH_RESIDUALS``), so that
    kernel runs once.  A layer without it saves nothing, as with no
    policy."""
    return jax.checkpoint(
        fn, prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS))


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x


def _ambient_mesh():
    """The abstract mesh the current trace runs under (``jax.set_mesh``);
    empty when no mesh is set."""
    return jax.sharding.get_abstract_mesh()


def _hint_spec(am, shape, axes):
    """PartitionSpec for ``shape`` on mesh ``am`` from :func:`shard_hint`'s
    per-dim ``axes`` entries."""
    from jax.sharding import PartitionSpec
    names = set(am.axis_names)
    sizes = dict(am.shape)
    spec = []
    for dim, a in zip(shape, axes):
        if a is None:
            spec.append(None)
            continue
        if a == "batch":
            cand = tuple(n for n in ("pod", "data") if n in names)
        elif isinstance(a, str):
            cand = (a,) if a in names else ()
        else:
            cand = tuple(n for n in a if n in names)
        total = 1
        for n in cand:
            total *= sizes[n]
        if cand and total > 1 and dim % total == 0:
            spec.append(cand if len(cand) > 1 else cand[0])
        else:
            spec.append(None)
    return PartitionSpec(*spec)


def shard_hint(x, *axes):
    """Best-effort activation sharding constraint on the ambient mesh.

    ``axes`` entries per dim: None | mesh-axis name | tuple of names |
    "batch" (resolves to the present data axes ("pod","data")).  Names not
    in the mesh or non-divisible dims are dropped, so the same model code
    runs un-meshed (smoke tests) and on any production mesh.
    """
    am = _ambient_mesh()
    if am.empty:
        return x
    return jax.lax.with_sharding_constraint(x, _hint_spec(am, x.shape, axes))


def cast_sharded(w, dtype, *axes):
    """Cast a weight to the compute dtype *while still sharded* (the hint
    pins the bf16 copy to the storage sharding, so FSDP all-gathers move
    bf16 instead of the fp32 master — half the wire bytes)."""
    return shard_hint(cast(w, dtype), *axes)


def _heads_shardable(n_heads: int) -> bool:
    """Whether the head dim divides the ambient model axis."""
    am = _ambient_mesh()
    if am.empty or "model" not in am.axis_names:
        return True
    return n_heads % dict(am.shape)["model"] == 0


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(b: ParamBuilder, d: int, kind: str) -> Params:
    p = {"scale": b.param((d,), ("embed",), init="ones")}
    if kind == "layer":
        p["bias"] = b.param((d,), ("embed",), init="zeros")
    return p


@scope("norm")
def apply_norm(p: Params, x: jnp.ndarray, kind: str,
               eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rms":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if kind == "layer":
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                      # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv      # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (chunked flash-style reference; GQA; causal/local windows)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B,Sq,KV,G,D)  k: (B,Sk,KV,D) -> (B,KV,G,Sq,Sk) in fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _mask_block(q_pos, k_pos, causal: bool, window: int) -> jnp.ndarray:
    """(Sq, Sk) boolean validity mask for absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    diff = q_pos[:, None] - k_pos[None, :]
    if causal:
        m &= diff >= 0
    if window > 0:
        m &= diff < window
    return m


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      q_offset: int = 0, softcap: float = 0.0,
                      block_k: int = 1024,
                      kv_valid_len: Optional[jnp.ndarray] = None):
    """Memory-efficient attention: lax.scan over key blocks, online softmax.

    q: (B, Sq, H, D); k: (B, Sk, KV, D); v: (B, Sk, KV, Dv) with H % KV
    == 0 (Dv may differ from D, as in latent attention).
    ``q_offset`` is the absolute position of q[0] (prefill continuation).
    ``kv_valid_len`` optionally masks out cache positions >= valid_len.
    Returns (B, Sq, H, Dv).

    This doubles as the oracle for the Pallas flash-attention kernel.
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    Dv = v.shape[-1]
    G = H // KV
    # TP plan: shard heads over the model axis when divisible; otherwise
    # fall back to *sequence* sharding of Q (context parallelism) — k/v
    # replicate (cheap under GQA, no head-repeat needed) and every score/
    # accumulator tile shards by q-position.  Without the fallback, archs
    # whose head count does not divide the model axis (llama4: 40, yi: 56,
    # llama3.2-3b: 24) get fully replicated attention (16x per-device
    # FLOPs).
    if _heads_shardable(H):
        if G > 1:
            # Repeat KV heads up to H so the head dim stays TP-shardable
            # (KV=8 does not divide a 16-way model axis; without this XLA
            # replicates the whole attention computation).  The GQA
            # bandwidth saving lives in the KV-cache layout, not here.
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
            KV, G = H, 1
        q = shard_hint(q, "batch", None, "model", None)
        k = shard_hint(k, "batch", None, "model", None)
        v = shard_hint(v, "batch", None, "model", None)
    else:
        q = shard_hint(q, "batch", "model", None, None)
    qr = q.reshape(B, Sq, KV, G, D) * (D ** -0.5)
    q_pos = q_offset + jnp.arange(Sq)

    bk = min(block_k, Sk)
    pad = (-Sk) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (Sk + pad) // bk
    kb = k.reshape(B, n_blocks, bk, KV, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, bk, KV, Dv).transpose(1, 0, 2, 3, 4)

    def step(carry, inputs):
        m_prev, l_prev, acc = carry
        kblk, vblk, blk_idx = inputs
        k_pos = blk_idx * bk + jnp.arange(bk)
        s = _gqa_scores(qr, kblk)                        # (B,KV,G,Sq,bk)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        valid = _mask_block(q_pos, k_pos, causal, window)
        valid &= (k_pos < Sk)[None, :]
        if kv_valid_len is not None:
            vl = jnp.asarray(kv_valid_len)
            vb_mask = k_pos[None, :] < vl[:, None]       # (B, bk)
            s = jnp.where(vb_mask[:, None, None, None, :], s, NEG_INF)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)                      # (B,KV,G,Sq)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe,
                                 NEG_INF))
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        # softmax weights travel at activation precision (halves the HBM
        # traffic of the dominant (.., Sq, bk) tensor); accumulation stays
        # fp32 via preferred_element_type.
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, KV, G, Dv), jnp.float32)
    (m_f, l_f, acc_f), _ = lax.scan(
        step, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks)))
    l_f = jnp.maximum(l_f, 1e-20).transpose(0, 3, 1, 2)[..., None]
    out = (acc_f / l_f).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, pos, window: int = 0,
                     softcap: float = 0.0):
    """Single-token attention against a cache.

    q: (B, 1, H, D); caches: (B, Smax, KV, D); pos: (B,) current index
    (the new token's position; cache entries >= pos+1 are invalid).
    """
    B, _, H, D = q.shape
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    qr = q.reshape(B, KV, G, D) * (D ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                   preferred_element_type=jnp.float32)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    k_pos = jnp.arange(Smax)[None, :]                    # (1, Smax)
    valid = k_pos <= pos[:, None]
    if window > 0:
        valid &= k_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + attention)
# ---------------------------------------------------------------------------

def init_attention(b: ParamBuilder, d_in: int, n_heads: int, n_kv: int,
                   head_dim: int, d_out: Optional[int] = None) -> Params:
    d_out = d_out or d_in
    return {
        "wq": b.param((d_in, n_heads, head_dim), ("embed", "heads", None)),
        "wk": b.param((d_in, n_kv, head_dim), ("embed", "kv", None)),
        "wv": b.param((d_in, n_kv, head_dim), ("embed", "kv", None)),
        "wo": b.param((n_heads, head_dim, d_out), ("heads", None, "embed")),
    }


def _attention_kernels() -> Optional[Dict[str, bool]]:
    """Keyword arguments for the Pallas attention kernels (and the grouped
    matmul of the expert layers), or None for the ``jnp`` path.  The compiled kernels run on a TPU backend; every other
    platform takes the ``jnp`` path.  Read while tracing, never at import,
    so the choice follows the backend the program is traced for."""
    return {"interpret": False} if jax.default_backend() == "tpu" else None


def _per_device(kernel, q, k, v):
    """Run a Pallas attention kernel on each device's share of q, k, v
    (B, S, heads, D) under the ambient mesh: batch rows over the data axes,
    heads over the model axis when the KV heads divide it.  The compiler
    does not partition a Mosaic kernel by itself."""
    am = _ambient_mesh()
    if am.empty:
        return kernel(q, k, v)
    heads = "model" if k.shape[2] % dict(am.shape).get("model", 1) == 0 \
        else None
    q_spec = _hint_spec(am, q.shape, ("batch", None, heads, None))
    kv_spec = _hint_spec(am, k.shape, ("batch", None, heads, None))
    return jax.shard_map(kernel, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


@scope("attn")
def attention_block(p: Params, x: jnp.ndarray, *, cfg_theta: float,
                    positional: str, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    block_k: int = 1024,
                    kv_x: Optional[jnp.ndarray] = None,
                    return_kv: bool = False,
                    kv_valid_len: Optional[jnp.ndarray] = None):
    """Full attention block over x (and optional cross-input kv_x).

    ``kv_valid_len`` (B,) masks key positions >= the per-row valid length —
    the batched bucketed prefill path, where rows are right-padded to a
    shared power-of-two bucket.
    """
    kv_src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x,
                   cast_sharded(p["wq"], x.dtype, "batch", "model", None))
    k = jnp.einsum("bsd,dhk->bshk", kv_src,
                   cast_sharded(p["wk"], x.dtype, "batch", "model", None))
    v = jnp.einsum("bsd,dhk->bshk", kv_src,
                   cast_sharded(p["wv"], x.dtype, "batch", "model", None))
    if positional == "rope":
        q = apply_rope(q, q_offset + jnp.arange(x.shape[1]), cfg_theta)
        if kv_x is None:
            k = apply_rope(k, q_offset + jnp.arange(kv_src.shape[1]),
                           cfg_theta)
    kernels = _attention_kernels()
    if (kernels is not None and softcap == 0.0 and q_offset == 0
            and kv_x is None and kv_valid_len is None):
        from ..kernels.flash_attention import flash_attention_train
        o = _per_device(lambda q, k, v: flash_attention_train(
            q, k, v, causal, window, min(block_k, q.shape[1]),
            min(block_k, k.shape[1]), kernels["interpret"]), q, k, v)
    else:
        o = chunked_attention(q, k, v, causal=causal and kv_x is None,
                              window=window, q_offset=q_offset,
                              softcap=softcap, block_k=block_k,
                              kv_valid_len=kv_valid_len)
    out = jnp.einsum("bshk,hkd->bsd", o,
                     cast_sharded(p["wo"], x.dtype, "model", None, "batch"))
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3)
# ---------------------------------------------------------------------------

def init_mla(b: ParamBuilder, d: int, n_heads: int, mla) -> Params:
    """Weights of a latent-attention block (``mla``: a ``MLAConfig``):
    queries straight from x, keys and values from one normalized latent."""
    H, r = n_heads, mla.kv_lora_rank
    nope, rope, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, \
        mla.v_head_dim
    return {
        "wq": b.param((d, H, nope + rope), ("embed", "heads", None)),
        "w_kva": b.param((d, r + rope), ("embed", None)),
        "kv_norm": init_norm(b, r, "rms"),
        "w_kvb": b.param((r, H, nope + dv), (None, "heads", None)),
        "wo": b.param((H, dv, d), ("heads", None, "embed"),
                      scale=(H * dv) ** -0.5),
    }


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           theta: float) -> jnp.ndarray:
    """RoPE on adjacent pairs ``(x[2i], x[2i+1])`` at frequency ``i``,
    returned with the evens first and the odds after them, as DeepSeek-V3's
    ``rope_interleave`` layout does.  x: (..., S, H, D)."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    return apply_rope(x, positions, theta)


@scope("attn")
def mla_attention_block(p: Params, x: jnp.ndarray, *, n_heads: int, mla,
                        theta: float, block_k: int = 1024) -> jnp.ndarray:
    """Causal latent attention over x: (B, S, d).

    ``q = x W_q`` (heads of nope + rope); ``[c, k_r] = x W_kva``, ``c``
    RMS-normalized; ``[k_nope, v] = c W_kvb``; RoPE on ``q_rope`` and
    ``k_r``, one ``k_r`` shared by every head; attention with q/k of
    nope + rope and v of ``v_head_dim``, scaled by (nope + rope) ** -0.5.
    """
    B, S, _ = x.shape
    r, nope, rope = mla.kv_lora_rank, mla.qk_nope_head_dim, \
        mla.qk_rope_head_dim
    dt = x.dtype
    pos = jnp.arange(S)
    q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], dt))
    q = jnp.concatenate(
        [q[..., :nope], apply_rope_interleaved(q[..., nope:], pos, theta)],
        axis=-1)
    kva = jnp.einsum("bsd,dr->bsr", x, cast(p["w_kva"], dt))
    c = apply_norm(p["kv_norm"], kva[..., :r], "rms")
    kv = jnp.einsum("bsr,rhk->bshk", c, cast(p["w_kvb"], dt))
    k_r = apply_rope_interleaved(kva[..., None, r:], pos, theta)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (B, S, n_heads, rope))],
        axis=-1)
    v = kv[..., nope:]
    kernels = _attention_kernels()
    if kernels is not None:
        from ..kernels.flash_attention import flash_attention_train
        blk = min(block_k, S)
        o = _per_device(lambda q, k, v: flash_attention_train(
            q, k, v, True, 0, blk, blk, kernels["interpret"]), q, k, v)
    else:
        o = chunked_attention(q, k, v, causal=True, block_k=block_k)
    return jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], dt))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(b: ParamBuilder, d: int, d_ff: int, activation: str) -> Params:
    if activation == "swiglu":
        return {
            "w_gate": b.param((d, d_ff), ("embed", "mlp")),
            "w_up": b.param((d, d_ff), ("embed", "mlp")),
            "w_down": b.param((d_ff, d), ("mlp", "embed")),
        }
    return {
        "w_up": b.param((d, d_ff), ("embed", "mlp")),
        "w_down": b.param((d_ff, d), ("mlp", "embed")),
    }


@scope("mlp")
def apply_mlp(p: Params, x: jnp.ndarray, activation: str) -> jnp.ndarray:
    wd = lambda w: cast_sharded(w, x.dtype, "batch", "model")
    if activation == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, wd(p["w_gate"]))
        u = jnp.einsum("bsd,df->bsf", x, wd(p["w_up"]))
        h = jax.nn.silu(g) * u
    else:
        u = jnp.einsum("bsd,df->bsf", x, wd(p["w_up"]))
        if activation == "gelu":
            h = jax.nn.gelu(u, approximate=True)
        elif activation == "relu2":
            h = jnp.square(jax.nn.relu(u))
        else:
            raise ValueError(activation)
    return jnp.einsum("bsf,fd->bsd", h,
                      cast_sharded(p["w_down"], x.dtype, "model", "batch"))


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-free capacity dispatch, EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(b: ParamBuilder, d: int, d_ff: int, n_experts: int,
             activation: str, shared_expert: bool) -> Params:
    def ep(shape, axes):
        return b.param(shape, axes)
    p = {"router": b.param((d, n_experts), ("embed", "experts"), scale=0.02)}
    if activation == "swiglu":
        p["w_gate"] = ep((n_experts, d, d_ff), ("experts", "embed", "mlp"))
        p["w_up"] = ep((n_experts, d, d_ff), ("experts", "embed", "mlp"))
        p["w_down"] = ep((n_experts, d_ff, d), ("experts", "mlp", "embed"))
    else:
        p["w_up"] = ep((n_experts, d, d_ff), ("experts", "embed", "mlp"))
        p["w_down"] = ep((n_experts, d_ff, d), ("experts", "mlp", "embed"))
    if shared_expert:
        p["shared"] = init_mlp(b, d, d_ff, activation)
    return p


@scope("mlp")
def apply_moe(p: Params, x: jnp.ndarray, *, n_experts: int, top_k: int,
              capacity_factor: float, activation: str,
              shared_expert: bool, drop: bool = True
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Token-choice top-k MoE with capacity-bounded scatter dispatch.

    x: (B, S, d).  Returns (y, aux) with load-balance / z-loss terms.
    The (E, C, d) dispatch buffers shard E over the model axis (EP); the
    scatter/gather pair lowers to the all-to-all-style exchange the paper's
    MoE kernels would see.

    ``drop=True`` is the training semantics (capacity-factor-bounded,
    overflow tokens dropped); serving paths use ``drop=False`` (capacity
    covers every routed token, so decode == prefill exactly).
    """
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, top_k)               # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    K = top_k
    E = n_experts
    C = max(int(capacity_factor * T * K / E), 1) if drop else T * K
    flat_e = top_e.reshape(T * K)
    flat_w = top_w.reshape(T * K)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # (T*K, E)
    pos_all = jnp.cumsum(oh, axis=0) - oh                 # rank within expert
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C

    tok_idx = jnp.repeat(jnp.arange(T), K)
    # dispatch = scatter of *indices* (int32, E*C words) + gather of
    # features.  Scattering the features themselves makes XLA move the
    # (T, d) tensor through a partial-sum all-reduce per layer; the index
    # indirection reduces the exchanged payload to the one unavoidable
    # token all-gather (the all-to-all).
    oob_pos = jnp.where(keep, pos, C)                     # C -> dropped
    slot_src = jnp.zeros((E, C), jnp.int32).at[flat_e, oob_pos].set(
        tok_idx + 1, mode="drop")
    slot_src = shard_hint(slot_src, "model", "batch")
    disp = jnp.where(
        (slot_src > 0)[..., None],
        jnp.take(xt.astype(x.dtype), jnp.maximum(slot_src - 1, 0),
                 axis=0),
        jnp.zeros((), x.dtype))
    safe_pos = jnp.where(keep, pos, 0)

    # expert MLPs (grouped einsum; E shards over the model axis, the
    # capacity dim over the data axes).  Without the hints XLA contracts
    # the FSDP-sharded d dim of the expert weights into a partial-sum
    # all-reduce of the (E, C, ff) hidden — tens of GB per layer.
    disp = shard_hint(disp, "model", "batch", None)
    if activation == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", disp, cast(p["w_gate"], x.dtype))
        u = jnp.einsum("ecd,edf->ecf", disp, cast(p["w_up"], x.dtype))
        h = jax.nn.silu(shard_hint(g, "model", "batch", None)) \
            * shard_hint(u, "model", "batch", None)
    else:
        u = jnp.einsum("ecd,edf->ecf", disp, cast(p["w_up"], x.dtype))
        u = shard_hint(u, "model", "batch", None)
        h = jax.nn.gelu(u, approximate=True) if activation == "gelu" \
            else jnp.square(jax.nn.relu(u))
    y_exp = jnp.einsum("ecf,efd->ecd", h, cast(p["w_down"], x.dtype))
    y_exp = shard_hint(y_exp, "model", "batch", None)

    gathered = y_exp[flat_e, safe_pos]                    # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    gathered = shard_hint(gathered, "batch", None)        # bf16 exchange
    # combine: tok_idx is repeat(arange(T), K), so the scatter-add is just
    # a (T, K, d) reshape-sum — no scatter, no fp32 all-reduce on the wire
    y = (gathered.reshape(T, K, d).astype(jnp.float32)
         * top_w[..., None]).sum(axis=1)
    y = y.reshape(B, S, d).astype(x.dtype)

    if shared_expert:
        y = y + apply_mlp(p["shared"], x, activation)

    # aux losses (Switch-style load balance + router z-loss)
    me = probs.mean(axis=0)                               # (E,)
    ce = (oh.sum(axis=0) / max(T * K, 1)).astype(jnp.float32)
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": jnp.mean(jnp.square(jax.scipy.special.logsumexp(
            logits, axis=-1))),
        "dropped_frac": 1.0 - keep.mean(),
    }
    return y, aux


# ---------------------------------------------------------------------------
# Dropless routed experts, of which this chip holds a share (DeepSeek-V3)
# ---------------------------------------------------------------------------

def init_routed_moe(b: ParamBuilder, d: int, d_ff: int, moe) -> Params:
    """Router over all ``moe.n_experts``, the selection bias (not trained),
    the ``moe.held`` experts this chip holds and the shared SwiGLU."""
    f = moe.d_expert or d_ff
    n = moe.held
    p = {"router": b.param((d, moe.n_experts), ("embed", None))}
    if moe.bias_rate:
        p["select_bias"] = b.param((moe.n_experts,), (None,), init="zeros")
    p["w_gate"] = b.param((n, d, f), ("experts", "embed", "mlp"),
                          scale=d ** -0.5)
    p["w_up"] = b.param((n, d, f), ("experts", "embed", "mlp"),
                        scale=d ** -0.5)
    p["w_down"] = b.param((n, f, d), ("experts", "mlp", "embed"),
                          scale=f ** -0.5)
    if moe.n_shared_experts:
        p["shared"] = init_mlp(b, d, moe.n_shared_experts * f, "swiglu")
    return p


# rows of the Pallas grouped matmul's tile, and the unit of the capped
# routed buffer (``routed_cap``)
_GMM_ROWS = 512


def _gmm_tiling(m: int, k: int, n: int):
    """Tiles of the Pallas grouped matmul: 512 rows (fewer where ``m`` is
    smaller), k up to 2048 and n up to 512.  On a TPU v5e at
    train-moonlight-ep8's shapes this was the fastest of six tilings, and
    42% faster forward and backward than ``lax.ragged_dot`` (PERF.md §6)."""
    return min(m, _GMM_ROWS), min(k, 2048), min(n, 512)


def grouped_matmul(rows: jnp.ndarray, w: jnp.ndarray,
                   sizes: jnp.ndarray) -> jnp.ndarray:
    """``rows`` (M, k) sorted by group: the first ``sizes[0]`` rows through
    ``w[0]`` (G, k, n), the next ``sizes[1]`` through ``w[1]``, and so on;
    the rows after the last group read zero and cost nothing.  On a TPU the
    Pallas grouped matmul (megablox ``gmm``; its kernels keep the named
    scope they run in), elsewhere ``lax.ragged_dot``."""
    kernels = _attention_kernels()
    if kernels is None:
        return lax.ragged_dot(rows, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    # the rows after the held groups form one more group, of no weight
    # here: the kernel visits only the groups of ``w`` and zeroes the rest
    rest = rows.shape[0] - sizes.sum()
    return gmm(rows, w, jnp.concatenate([sizes, rest[None]]), rows.dtype,
               _gmm_tiling, interpret=kernels["interpret"])


def routed_cap(tokens: int, top_k: int, held: int,
               n_experts: int) -> Optional[int]:
    """Rows of the capped buffer of an expert layer whose chip holds
    ``held`` of ``n_experts`` experts, or None where no cap applies (the
    buffer then holds all ``tokens * top_k`` copies)."""
    # twice the rows the held experts expect from an even draw, in whole
    # row tiles of the grouped matmul: at train-moonlight-ep8's shapes
    # 12,288 of the 49,152 copies, where the worst uniform draw on record
    # (6,288 rows) leaves 1.95x headroom and the selection bias keeps the
    # loads near even in training.  A chip holding half the experts or
    # more keeps the buffer of all copies.
    expected = -(-2 * tokens * top_k * held // n_experts)
    cap = -(-expected // _GMM_ROWS) * _GMM_ROWS
    return cap if cap < tokens * top_k else None


def _experts_swiglu(rows, w_gate, w_up, w_down, sizes):
    g = grouped_matmul(rows, w_gate, sizes)
    u = grouped_matmul(rows, w_up, sizes)
    return grouped_matmul(jax.nn.silu(g) * u, w_down, sizes)


def _to_tokens(rows: jnp.ndarray, src: jnp.ndarray, pos: jnp.ndarray,
               w: Optional[jnp.ndarray]) -> jnp.ndarray:
    """``sum_k w[t, k] * (the row of copy (t, k))``, (T, d) in float32 with
    ``w`` 1 where None: the rows of a routed buffer, row ``c`` holding copy
    ``src[c]`` (an index into the T*K copies, ``pos`` (T, K) their rows),
    added into their tokens.  A scatter-add of the buffer's rows, which
    timed faster at train-moonlight-ep8's capped shapes than gathering the
    T*K copies from it (PERF.md §6); the buffer's rows of no held copy
    are 0."""
    T, K = pos.shape
    rows = rows.astype(jnp.float32)
    if w is not None:
        rows = rows * jnp.take(w.reshape(-1), src)[:, None]
    return jnp.zeros((T, rows.shape[1]), jnp.float32).at[src // K].add(rows)


@jax.custom_vjp
def _dispatch_held(x: jnp.ndarray, src: jnp.ndarray,
                   pos: jnp.ndarray) -> jnp.ndarray:
    """The routed buffer: row ``c`` is the token of copy ``src[c]``.  Its
    gradient adds each row into its token (:func:`_to_tokens`)."""
    return jnp.take(x, src // pos.shape[1], axis=0)


def _dispatch_held_fwd(x, src, pos):
    return _dispatch_held(x, src, pos), (src, pos)


def _dispatch_held_bwd(res, g):
    src, pos = res
    return _to_tokens(g, src, pos, None).astype(g.dtype), None, None


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@jax.custom_vjp
def _combine_held(y_rows: jnp.ndarray, w: jnp.ndarray, src: jnp.ndarray,
                  pos: jnp.ndarray) -> jnp.ndarray:
    """Each token's copies from the routed buffer ``y_rows``, weighted by
    ``w`` (T, K) and summed in float32 (:func:`_to_tokens`), in the rows'
    dtype.  Its gradients gather ``len(y_rows)`` rows, where a
    scatter-add's would gather all T*K copies."""
    return _to_tokens(y_rows, src, pos, w).astype(y_rows.dtype)


def _combine_held_fwd(y_rows, w, src, pos):
    return _combine_held(y_rows, w, src, pos), (y_rows, w, src, pos)


def _combine_held_bwd(res, g):
    y_rows, w, src, pos = res
    g_rows = jnp.take(g, src // w.shape[1], axis=0).astype(jnp.float32)
    d_rows = g_rows * jnp.take(w.reshape(-1), src)[:, None]
    d_w = jnp.sum(g_rows * y_rows.astype(jnp.float32), axis=-1)
    d_w = jnp.take(d_w, pos.reshape(-1), mode="fill",
                   fill_value=0).reshape(w.shape)
    return d_rows.astype(y_rows.dtype), d_w, None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def _routed_held(cap, xt, w_gate, w_up, w_down, top_w, order, back, sizes):
    """The held experts' part of the layer, (T, d), through a buffer of
    ``cap`` rows, which must hold every copy routed here.  ``order`` (with
    inverse ``back``) sorts the T*K token copies by expert, the copies
    routed elsewhere last, so the buffer is the first ``cap`` of ``order``;
    its rows after the held copies feed no group and come out of the
    grouped matmuls 0.  Back in token order, each token's copies are summed
    in float32 by their weights ``top_w`` (T, K)."""
    T, K = top_w.shape
    with scope("dispatch"):
        src = order[:cap]
        pos = jnp.where(back < sizes.sum(), back, cap).reshape(T, K)
        rows = _dispatch_held(xt, src, pos)
    with scope("experts"):
        y_rows = _experts_swiglu(rows, w_gate, w_up, w_down, sizes)
    with scope("dispatch"):
        return _combine_held(y_rows, top_w, src, pos)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed_capped(cap, xt, w_gate, w_up, w_down, top_w, order, back,
                   sizes):
    """:func:`_routed_held` over ``cap`` rows where the copies held here
    fit them, else over all T*K copies: no copy is ever dropped.  The
    gradient keeps only the inputs and runs the taken branch again inside,
    so that the untaken branch leaves no buffers (differentiating a
    ``lax.cond`` keeps both branches' residuals)."""
    return lax.cond(sizes.sum() <= cap, functools.partial(_routed_held, cap),
                    functools.partial(_routed_held, order.size), xt, w_gate,
                    w_up, w_down, top_w, order, back, sizes)


def _routed_capped_fwd(cap, *args):
    return _routed_capped(cap, *args), args


def _routed_capped_bwd(cap, args, g):
    *inputs, order, back, sizes = args

    def pull(branch):
        def run(*inputs):
            _, vjp = jax.vjp(lambda *a: branch(*a, order, back, sizes),
                             *inputs)
            return vjp(g)
        return run

    grads = lax.cond(sizes.sum() <= cap,
                     pull(functools.partial(_routed_held, cap)),
                     pull(functools.partial(_routed_held, order.size)),
                     *inputs)
    return (*grads, None, None, None)


_routed_capped.defvjp(_routed_capped_fwd, _routed_capped_bwd)


@scope("mlp")
def apply_routed_moe(p: Params, x: jnp.ndarray, *, n_experts: int,
                     top_k: int, routed_scale: float, first: int = 0
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Dropless top-k routing over all ``n_experts``; this chip computes the
    part of the result that its experts ``first .. first + held - 1`` give,
    plus the shared expert.  x: (B, S, d).

    Scores ``s`` are ``sigmoid(x W_r)`` in float32; the top
    ``top_k`` of ``s + select_bias`` are chosen, weighted by ``s`` over
    their sum times ``routed_scale``.  The token-expert assignments held
    here are sorted by expert and run as grouped matmuls; no token is
    dropped.  Where the chip holds fewer than half the experts they are
    gathered into a buffer of ``routed_cap`` rows, and into one of all
    T*K copies only when more are routed here.  Returns (y, aux): the
    sequence-wise balance loss over all experts (DeepSeek-V3 eqs. 17-20,
    without its weight), each expert's load (assignments of these tokens),
    the rows computed here, the largest held expert's rows, and
    ``routed_overflow``: 1 where the buffer of all T*K copies ran (always
    where no cap applies), else 0.
    """
    B, S, d = x.shape
    T, K = B * S, top_k
    n = p["w_gate"].shape[0]
    dt = x.dtype
    xt = x.reshape(T, d)
    with scope("router"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        s = jax.nn.sigmoid(logits)
        choice = s + p["select_bias"] if "select_bias" in p else s
        _, top_e = lax.top_k(lax.stop_gradient(choice), K)    # (T, K)
        top_w = jnp.take_along_axis(s, top_e, axis=1)
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20) \
            * routed_scale
    with scope("dispatch"):
        local = top_e.reshape(T * K) - first
        group = jnp.where((local >= 0) & (local < n), local, n)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=n + 1)[:n]
        back = jnp.zeros((T * K,), order.dtype).at[order].set(
            jnp.arange(T * K, dtype=order.dtype))
    with scope("experts"):
        experts = [cast(p[k], dt) for k in ("w_gate", "w_up", "w_down")]
    cap = routed_cap(T, K, n, n_experts)
    if cap is None:
        y = _routed_held(T * K, xt, *experts, top_w, order, back, sizes)
        overflow = jnp.ones((), jnp.float32)
    else:
        y = _routed_capped(cap, xt, *experts, top_w, order, back, sizes)
        overflow = (sizes.sum() > cap).astype(jnp.float32)
    y = y.reshape(B, S, d)
    if "shared" in p:
        with scope("shared"):
            y = y + apply_mlp(p["shared"], x, "swiglu")
    with scope("router"):
        chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32).sum(1)
        load = chosen.sum(0)                                   # (E,)
        # per sequence: f_i = E / (K S) * picks of i, P_i = mean of the
        # scores normalized over all experts
        f = chosen.reshape(B, S, n_experts).sum(1) * n_experts / (K * S)
        pr = (s / s.sum(-1, keepdims=True)).reshape(B, S, n_experts)
        balance = jnp.mean(jnp.sum(f * pr.mean(1), axis=-1))
    aux = {"balance": balance, "expert_load": load,
           "routed_rows_here": sizes.sum().astype(jnp.float32),
           "max_expert_load": sizes.max().astype(jnp.float32),
           "routed_overflow": overflow}
    return y, aux


# ---------------------------------------------------------------------------
# KV/state cache slot updates (continuous batching)
# ---------------------------------------------------------------------------

def write_cache_slot(pool: Params, sub: Params, slot, axes: Params) -> Params:
    """Write a single-sequence cache ``sub`` (batch dim of size 1) into row
    ``slot`` of the pooled cache ``pool``.

    ``axes`` mirrors the cache tree with the integer batch-axis position of
    each leaf (models expose it via ``cache_slot_axes()``; most leaves are
    ``(layers, B, ...)`` but e.g. grouped local ring buffers are
    ``(groups, sub, B, ...)``).  ``slot`` may be a traced scalar, so the
    admission path jits cleanly.
    """
    def upd(p, s, ax):
        return lax.dynamic_update_slice_in_dim(p, s.astype(p.dtype),
                                               slot, axis=ax)
    return jax.tree.map(upd, pool, sub, axes)


def write_cache_slots(pool: Params, sub: Params, slots: jnp.ndarray,
                      axes: Params) -> Params:
    """Batched :func:`write_cache_slot`: scatter an N-sequence sub-cache
    into rows ``slots`` (N,) of the pooled cache in one op per leaf.

    Out-of-range slot indices are *dropped* — the batched-admission path
    pads its row count to a fixed width with dummy rows carrying
    ``slot == n_slots``, so jit sees one shape per prompt bucket while the
    pool only ever receives the real rows.
    """
    def upd(p, s, ax):
        pm = jnp.moveaxis(p, ax, 0)
        sm = jnp.moveaxis(s.astype(p.dtype), ax, 0)
        return jnp.moveaxis(pm.at[slots].set(sm, mode="drop"), 0, ax)
    return jax.tree.map(upd, pool, sub, axes)


# ---------------------------------------------------------------------------
# Paged KV cache (block-table indirection; serve/kv_pages.py owns the pool)
# ---------------------------------------------------------------------------

def paged_cache_write(pages: jnp.ndarray, new: jnp.ndarray,
                      block_tables: jnp.ndarray, pos: jnp.ndarray
                      ) -> jnp.ndarray:
    """Write one token's K (or V) per slot into the page pool.

    pages: (P, page, KV, D); new: (B, KV, D); block_tables: (B, nb);
    pos: (B,) absolute position being written.  Distinct slots own
    distinct pages, so the row scatter never collides.
    """
    page = pages.shape[1]
    arangeB = jnp.arange(new.shape[0])
    pids = block_tables[arangeB, pos // page]            # (B,)
    return pages.at[pids, pos % page].set(new.astype(pages.dtype))


def kv_qmax(dtype) -> float:
    """Clip point of a quantized KV storage dtype (127 for int8, the max
    finite for float8 variants)."""
    d = jnp.dtype(dtype)
    if jnp.issubdtype(d, jnp.integer):
        return float(jnp.iinfo(d).max)
    return float(jnp.finfo(d).max)


def paged_cache_write_quant(pages: jnp.ndarray, scales: jnp.ndarray,
                            new: jnp.ndarray, block_tables: jnp.ndarray,
                            pos: jnp.ndarray):
    """Quantizing variant of :func:`paged_cache_write`.

    pages: (P, page, KV, D) in a quantized dtype; scales: (P, KV) float32
    per-(page, KV-head) absmax scales; new: (B, KV, D).  Returns
    ``(pages, scales)``.

    Scale discipline: the first write into a page (``pos % page == 0``)
    resets its scale to the token's absmax (and zeroes the page's stale
    remainder); later writes only *widen* the scale
    (``max(old, absmax/qmax)``), re-quantizing the page's existing
    entries in-register when it moves.  Widening is monotone within a
    page, so a token never loses precision to a later smaller one.
    """
    page = pages.shape[1]
    B = new.shape[0]
    arangeB = jnp.arange(B)
    qmax = kv_qmax(pages.dtype)
    is_int = jnp.issubdtype(pages.dtype, jnp.integer)
    pids = block_tables[arangeB, pos // page]            # (B,)
    off = pos % page                                     # (B,)
    newf = new.astype(jnp.float32)
    tok_scale = jnp.maximum(
        jnp.max(jnp.abs(newf), axis=-1) / qmax, 1e-8)    # (B, KV)
    eff_old = jnp.where((off == 0)[:, None], 0.0, scales[pids])
    new_scale = jnp.maximum(eff_old, tok_scale)
    # re-quantize the page onto the (possibly wider) scale; ratio == 1
    # when the scale did not move, 0 on a page's first write (erasing the
    # previous tenant's stale values)
    ratio = (eff_old / new_scale)[:, None, :, None]
    block = pages[pids].astype(jnp.float32) * ratio      # (B, page, KV, D)
    if is_int:
        block = jnp.round(block)
    q_tok = newf / new_scale[:, :, None]
    if is_int:
        q_tok = jnp.round(q_tok)
    block = block.at[arangeB, off].set(q_tok)
    block = jnp.clip(block, -qmax, qmax).astype(pages.dtype)
    # distinct live slots own distinct pages; only frozen slots collide,
    # and only on the parking page no live request reads
    return (pages.at[pids].set(block),
            scales.at[pids].set(new_scale))


def paged_decode_attention(q, k_pages, v_pages, block_tables, *,
                           pos, window: int = 0, softcap: float = 0.0,
                           k_scales=None, v_scales=None):
    """Single-token attention against a paged cache.

    q: (B, 1, H, D); pools (P, page, KV, D); block_tables (B, nb);
    pos (B,).  Dispatches to the Pallas page-read kernel on a TPU backend
    (the block table feeds the DMA index map — no materialized gather),
    else to the gather-based jnp reference.  With
    ``k_scales``/``v_scales`` (P, KV) the pools hold quantized values
    and K/V are dequantized in-register (fused into the kernel — the
    HBM read stays at the quantized width).
    """
    from ..kernels.flash_attention import (paged_attention_ref,
                                           paged_flash_decode)
    kernels = _attention_kernels()
    if kernels is not None:
        return paged_flash_decode(q, k_pages, v_pages, block_tables, pos,
                                  window=window, softcap=softcap,
                                  k_scales=k_scales, v_scales=v_scales,
                                  **kernels)
    return paged_attention_ref(q, k_pages, v_pages, block_tables, pos,
                               window=window, softcap=softcap,
                               k_scales=k_scales, v_scales=v_scales)


def gather_last_positions(x: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, d); lens: (B,) valid lengths. Returns (B, 1, d) at the
    last *valid* position per row (right-padded batched prefill)."""
    idx = jnp.clip(lens - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def gather_tail_window(x: jnp.ndarray, lens: jnp.ndarray,
                       W: int) -> jnp.ndarray:
    """Last-``W`` *valid* positions per row, zero-filled left of 0.

    x: (B, S, ...); lens: (B,).  Returns (B, W, ...) holding positions
    ``lens-W .. lens-1`` — the conv-window tail of a right-padded batched
    prefill, where ``x[:, -W:]`` would capture padding instead.
    """
    pos = lens[:, None] - W + jnp.arange(W)[None, :]      # (B, W)
    idx = jnp.clip(pos, 0, x.shape[1] - 1)
    gathered = jnp.take_along_axis(
        x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)), axis=1)
    keep = (pos >= 0).reshape(pos.shape + (1,) * (x.ndim - 2))
    return jnp.where(keep, gathered, jnp.zeros((), x.dtype))


def gather_ring_window(k: jnp.ndarray, lens: jnp.ndarray,
                       W: int) -> jnp.ndarray:
    """Ring-arranged last-``W`` window: slot ``p % W`` holds position p.

    k: (B, S, ...); lens: (B,) per-row valid lengths.  Ring slot ``r``
    receives the largest valid position congruent to r mod W (zeros where
    that position is negative) — the per-row generalization of
    ``roll(k[:, -W:], S % W)`` used when every row has length S.
    """
    r = jnp.arange(W)[None, :]
    last = lens[:, None] - 1
    pos = last - ((last - r) % W)                         # (B, W)
    idx = jnp.clip(pos, 0, k.shape[1] - 1)
    gathered = jnp.take_along_axis(
        k, idx.reshape(idx.shape + (1,) * (k.ndim - 2)), axis=1)
    keep = (pos >= 0).reshape(pos.shape + (1,) * (k.ndim - 2))
    return jnp.where(keep, gathered, jnp.zeros((), k.dtype))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(b: ParamBuilder, vocab: int, d: int,
                   tie: bool, max_seq: int = 0, learned_pos: bool = False
                   ) -> Params:
    p = {"wte": b.param((vocab, d), ("vocab", "embed"), scale=0.02)}
    if learned_pos:
        p["wpe"] = b.param((max_seq, d), (None, "embed"), scale=0.02)
    if not tie:
        p["head"] = b.param((d, vocab), ("embed", "vocab"), scale=0.02)
    return p


@scope("embed")
def embed_tokens(p: Params, tokens: jnp.ndarray, dtype,
                 pos_offset: int = 0) -> jnp.ndarray:
    x = jnp.take(p["wte"], tokens, axis=0).astype(dtype)
    if "wpe" in p:
        pos = pos_offset + jnp.arange(tokens.shape[-1])
        x = x + jnp.take(p["wpe"], pos, axis=0).astype(dtype)
    return x


_VOCAB_PAD = 512


@scope("head")
def unembed(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Project to vocab logits, padded to a 512-multiple so the vocab dim
    shards over the model axis (odd vocab sizes — 50280, 49155, 151655... —
    otherwise force an unsharded (B,S,V) logits tensor and a multi-GB
    all-gather per step).  Pad columns are masked to -1e30."""
    if "head" in p:
        w = cast(p["head"], x.dtype)                  # (d, V)
        V = w.shape[1]
        Vp = -(-V // _VOCAB_PAD) * _VOCAB_PAD
        if Vp != V:
            w = jnp.pad(w, ((0, 0), (0, Vp - V)))
        logits = jnp.einsum("bsd,dv->bsv", x, w)
    else:
        w = cast(p["wte"], x.dtype)                   # (V, d)
        V = w.shape[0]
        Vp = -(-V // _VOCAB_PAD) * _VOCAB_PAD
        if Vp != V:
            w = jnp.pad(w, ((0, Vp - V), (0, 0)))
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    logits = shard_hint(logits, "batch", None, "model")
    if Vp != V:
        logits = jnp.where(jnp.arange(Vp) < V, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None,
                          z_loss: float = 0.0):
    """Token-level CE in fp32 with optional z-loss; logits (B,S,V)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is None:
        denom = nll.size
        total = nll.sum()
    else:
        mask = mask.astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        total = (nll * mask).sum()
    return total / denom
