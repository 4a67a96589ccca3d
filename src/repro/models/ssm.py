"""Mamba2 (SSD — state-space duality) blocks and LM stack.

Implements the chunked SSD algorithm of arXiv:2405.21060: intra-chunk dual
(quadratic-in-chunk) form + inter-chunk linear state recurrence, giving
O(S·Q) compute and O(1)-state decode.  ``ssd_chunked`` is also the oracle
for the Pallas ``ssd_scan`` kernel.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig, ShapeConfig
from . import common as cm
from .common import ParamBuilder, Params

_DT_BIAS = -4.6  # softplus^-1(0.01): default timestep at init


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_chunked(xb: jnp.ndarray, a: jnp.ndarray, Bm: jnp.ndarray,
                Cm: jnp.ndarray, chunk: int,
                h0: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan.

    xb: (B,S,H,P) dt-scaled inputs; a: (B,S,H) log-decay (dt*A, negative);
    Bm, Cm: (B,S,G,N) input/output projections (G groups, H % G == 0).
    h0: optional initial state (B,H,N,P).
    Returns (y: (B,S,H,P), final_state: (B,H,N,P)).
    """
    B, S, H, P = xb.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // Q

    f32 = jnp.float32
    xg = xb.reshape(B, nc, Q, G, hpg, P).astype(f32)
    ag = a.reshape(B, nc, Q, G, hpg).astype(f32)
    Bg = Bm.reshape(B, nc, Q, G, N).astype(f32)
    Cg = Cm.reshape(B, nc, Q, G, N).astype(f32)

    a_cs = jnp.cumsum(ag, axis=2)                      # inclusive cumsum
    a_tot = a_cs[:, :, -1]                             # (B,nc,G,hpg)

    # ---- intra-chunk (dual / attention-like quadratic form) ----
    CB = jnp.einsum("bnqgi,bnkgi->bngqk", Cg, Bg)      # (B,nc,G,Q,Q)
    # a_cs: (B,nc,Q,G,hpg); seg[q,k] = a_cs[q] - a_cs[k]
    seg = (a_cs[:, :, :, None, :, :] - a_cs[:, :, None, :, :, :])
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None, None, :, :, None, None], jnp.exp(seg), 0.0)
    y_intra = jnp.einsum("bngqk,bnqkgh,bnkghp->bnqghp", CB, L, xg)

    # ---- chunk states ----
    decay_out = jnp.exp(a_tot[:, :, None] - a_cs)      # (B,nc,Q,G,hpg)
    S_c = jnp.einsum("bnkgi,bnkgh,bnkghp->bnghip", Bg, decay_out, xg)

    # ---- inter-chunk recurrence ----
    if h0 is None:
        h0 = jnp.zeros((B, G, hpg, N, P), f32)
    else:
        h0 = h0.reshape(B, G, hpg, N, P).astype(f32)

    def step(h, inp):
        s_c, atot = inp                                # (B,G,hpg,N,P),(B,G,hpg)
        h_new = jnp.exp(atot)[..., None, None] * h + s_c
        return h_new, h                                # emit state *entering*

    a_tot_t = jnp.moveaxis(a_tot, 1, 0)                # (nc,B,G,hpg)
    S_c_t = jnp.moveaxis(S_c, 1, 0)                    # (nc,B,G,hpg,N,P)
    h_final, h_prev = lax.scan(step, h0, (S_c_t, a_tot_t))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                # (B,nc,G,hpg,N,P)

    decay_in = jnp.exp(a_cs)                           # (B,nc,Q,G,hpg)
    y_off = jnp.einsum("bnqgi,bnqgh,bnghip->bnqghp", Cg, decay_in, h_prev)

    y = (y_intra + y_off).reshape(B, nc * Q, H, P)[:, :S]
    return y.astype(xb.dtype), h_final.reshape(B, H, N, P)


def ssd_decode_step(h: jnp.ndarray, x: jnp.ndarray, a: jnp.ndarray,
                    Bm: jnp.ndarray, Cm: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token SSD recurrence.

    h: (B,H,N,P) state; x: (B,H,P) dt-scaled input; a: (B,H) log decay;
    Bm, Cm: (B,G,N).  Returns (y: (B,H,P), h_new).
    """
    B, H, N, P = h.shape
    G = Bm.shape[1]
    hpg = H // G
    hr = h.reshape(B, G, hpg, N, P)
    xr = x.reshape(B, G, hpg, P).astype(jnp.float32)
    ar = a.reshape(B, G, hpg).astype(jnp.float32)
    upd = jnp.einsum("bgi,bghp->bghip", Bm.astype(jnp.float32), xr)
    h_new = jnp.exp(ar)[..., None, None] * hr + upd
    y = jnp.einsum("bgi,bghip->bghp", Cm.astype(jnp.float32), h_new)
    return (y.reshape(B, H, P).astype(x.dtype),
            h_new.reshape(B, H, N, P))


# ---------------------------------------------------------------------------
# Causal depthwise conv
# ---------------------------------------------------------------------------

def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray):
    """x: (B,S,C), w: (W,C), b: (C,). Left-padded depthwise causal conv."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out + b.astype(x.dtype)


def conv_decode_step(window: jnp.ndarray, x_new: jnp.ndarray,
                     w: jnp.ndarray, b: jnp.ndarray):
    """window: (B,W-1,C) past inputs; x_new: (B,C). Returns (y, new_window)."""
    full = jnp.concatenate([window, x_new[:, None]], axis=1)  # (B,W,C)
    y = jnp.einsum("bwc,wc->bc", full, w.astype(x_new.dtype)) \
        + b.astype(x_new.dtype)
    return y, full[:, 1:]


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def init_mamba_block(b: ParamBuilder, cfg: ModelConfig) -> Params:
    # Projections are kept *separate* (z / x / BC / dt) and the depthwise
    # conv runs per segment: a fused in_proj would be split at non-shard-
    # aligned channel boundaries, forcing collective-permute resharding in
    # every layer (depthwise conv is per-channel, so splitting it is
    # mathematically identical).
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.head_dim
    bc = 2 * s.n_groups * s.state_dim
    return {
        "w_z": b.param((d, d_in), ("embed", "inner")),
        "w_x": b.param((d, d_in), ("embed", "inner")),
        "w_bc": b.param((d, bc), ("embed", "inner")),
        "w_dt": b.param((d, nh), ("embed", None)),
        "conv_x_w": b.param((s.conv_width, d_in), (None, "inner"),
                            scale=0.5),
        "conv_x_b": b.param((d_in,), ("inner",), init="zeros"),
        "conv_bc_w": b.param((s.conv_width, bc), (None, "inner"),
                             scale=0.5),
        "conv_bc_b": b.param((bc,), ("inner",), init="zeros"),
        "dt_bias": b.param((nh,), (None,), init="zeros"),
        "A_log": b.param((nh,), (None,), init="zeros"),
        "D": b.param((nh,), (None,), init="ones"),
        "gate_norm": {"scale": b.param((d_in,), ("inner",), init="ones")},
        "out_proj": b.param((d_in, d), ("inner", "embed")),
    }


def mamba_block(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                h0=None, return_state: bool = False,
                seq_lens: Optional[jnp.ndarray] = None):
    """Full-sequence Mamba2 block. x: (B,S,d).

    ``seq_lens`` (B,) marks per-row valid lengths for right-padded batched
    prefill: padded positions get ``dt = 0``, making the SSD recurrence an
    identity there (``exp(0)·h + B·(x·0) = h``), so the final state equals
    the state at each row's true length; the conv tail is gathered from
    the last valid positions instead of the padded end.
    """
    s = cfg.ssm
    B_, S, d = x.shape
    d_in = s.expand * d
    nh = d_in // s.head_dim
    gn = s.n_groups * s.state_dim
    z = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_z"], x.dtype))
    xs = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_x"], x.dtype))
    bc = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_bc"], x.dtype))
    dt = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_dt"], x.dtype))
    conv_in = jnp.concatenate([xs, bc], axis=-1)
    xs = jax.nn.silu(causal_conv(xs, p["conv_x_w"], p["conv_x_b"]))
    bc_c = jax.nn.silu(causal_conv(bc, p["conv_bc_w"], p["conv_bc_b"]))
    bc_c = cm.shard_hint(bc_c, "batch", None, None)  # small; replicate
    Bm, Cm = jnp.split(bc_c, [gn], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32) + _DT_BIAS)
    if seq_lens is not None:
        # padded positions: dt=0 -> log-decay 0 and zero input update,
        # i.e. the recurrence is the identity past each row's length
        seq_mask = jnp.arange(S)[None, :] < seq_lens[:, None]
        dt = dt * seq_mask[..., None]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))       # (nh,)
    a = dt * A                                         # (B,S,nh) log decay
    xh = xs.reshape(B_, S, nh, s.head_dim)
    xb = xh * dt[..., None].astype(xh.dtype)
    Bg = Bm.reshape(B_, S, s.n_groups, s.state_dim)
    Cg = Cm.reshape(B_, S, s.n_groups, s.state_dim)
    y, h_final = ssd_chunked(xb, a, Bg, Cg, s.chunk_size, h0=h0)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(B_, S, d_in)
    y = cm.apply_norm(p["gate_norm"], y * jax.nn.silu(z), "rms")
    out = jnp.einsum("bsi,id->bsd", y, cm.cast(p["out_proj"], x.dtype))
    if return_state:
        # conv tail: last (W-1) post-activation *inputs* of the conv,
        # taken at each row's true end when lengths are ragged
        if seq_lens is not None:
            tail = cm.gather_tail_window(conv_in, seq_lens,
                                         s.conv_width - 1)
        else:
            tail = conv_in[:, -(s.conv_width - 1):]
            if S < s.conv_width - 1:
                tail = jnp.pad(tail,
                               ((0, 0), (s.conv_width - 1 - S, 0), (0, 0)))
        return out, (h_final, tail)
    return out


def mamba_decode_step(p: Params, x: jnp.ndarray, cache, cfg: ModelConfig):
    """One-token Mamba2 step. x: (B,1,d); cache = (ssm_state, conv_window).

    The conv window stores concat(x_seg, bc_seg) raw conv inputs; the two
    depthwise convs run on their own segments (identical to the fused
    form)."""
    s = cfg.ssm
    h, conv_win = cache
    B_, _, d = x.shape
    d_in = s.expand * d
    nh = d_in // s.head_dim
    gn = s.n_groups * s.state_dim
    z = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_z"], x.dtype))[:, 0]
    xs = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_x"], x.dtype))[:, 0]
    bc = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_bc"], x.dtype))[:, 0]
    dt = jnp.einsum("bsd,dp->bsp", x, cm.cast(p["w_dt"], x.dtype))[:, 0]
    conv_in = jnp.concatenate([xs, bc], axis=-1)       # (B, C)
    xs_out, win_x = conv_decode_step(conv_win[..., :d_in], xs,
                                     p["conv_x_w"], p["conv_x_b"])
    bc_out, win_bc = conv_decode_step(conv_win[..., d_in:], bc,
                                      p["conv_bc_w"], p["conv_bc_b"])
    conv_win = jnp.concatenate([win_x, win_bc], axis=-1)
    xs = jax.nn.silu(xs_out)
    Bm, Cm = jnp.split(jax.nn.silu(bc_out), [gn], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32) + _DT_BIAS)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = dt * A                                         # (B,nh)
    xh = xs.reshape(B_, nh, s.head_dim)
    xb = xh * dt[..., None].astype(xh.dtype)
    Bg = Bm.reshape(B_, s.n_groups, s.state_dim)
    Cg = Cm.reshape(B_, s.n_groups, s.state_dim)
    y, h = ssd_decode_step(h, xb, a, Bg, Cg)
    y = y + p["D"].astype(y.dtype)[None, :, None] * xh
    y = y.reshape(B_, d_in)
    y = cm.apply_norm(p["gate_norm"], y * jax.nn.silu(z), "rms")
    out = jnp.einsum("bi,id->bd", y, cm.cast(p["out_proj"], x.dtype))
    return out[:, None], (h, conv_win)


# ---------------------------------------------------------------------------
# Mamba2 LM (mamba2-370m)
# ---------------------------------------------------------------------------

class MambaLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        s = cfg.ssm
        self.d_inner = s.expand * cfg.d_model
        self.nh = self.d_inner // s.head_dim
        self.conv_ch = self.d_inner + 2 * s.n_groups * s.state_dim

    def _build(self, mode, rng=None):
        cfg = self.cfg
        b = ParamBuilder(mode, rng, dtype=self.param_dtype)
        params = {
            "embed": cm.init_embedding(b, cfg.vocab_size, cfg.d_model,
                                       cfg.tie_embeddings),
            "final_norm": cm.init_norm(b, cfg.d_model, cfg.norm),
        }

        def layer(bb):
            return {"norm": cm.init_norm(bb, cfg.d_model, cfg.norm),
                    "mamba": init_mamba_block(bb, cfg)}

        if mode == ParamBuilder.INIT:
            layers = [layer(b) for _ in range(cfg.n_layers)]
            params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                            *layers)
        else:
            from .transformer import _stack_tree
            params["layers"] = _stack_tree(layer(b), cfg.n_layers, mode)
        return params

    def init(self, rng):
        return self._build(ParamBuilder.INIT, rng)

    def abstract_params(self):
        return self._build(ParamBuilder.ABSTRACT)

    def param_axes(self):
        return self._build(ParamBuilder.AXES)

    def forward_hidden(self, params, x, remat: bool = True):
        cfg = self.cfg

        def body(x, lp):
            h = cm.apply_norm(lp["norm"], x, cfg.norm)
            return x + mamba_block(lp["mamba"], h, cfg), None

        if remat:
            body = cm.remat(body)
        x, _ = lax.scan(body, x, params["layers"])
        return x, {}

    def loss(self, params, batch, rng=None, remat: bool = True):
        x = cm.embed_tokens(params["embed"], batch["tokens"],
                            self.compute_dtype)
        x, _ = self.forward_hidden(params, x, remat=remat)
        x = cm.apply_norm(params["final_norm"], x, self.cfg.norm)
        logits = cm.unembed(params["embed"], x)
        loss = cm.softmax_cross_entropy(logits, batch["targets"],
                                        batch.get("mask"), z_loss=1e-4)
        return loss, {"loss": loss, "ce_loss": loss}

    # -- serving --------------------------------------------------------
    def _cache_struct(self, B, max_seq=0):
        cfg = self.cfg
        s = cfg.ssm
        dt = self.compute_dtype
        return {
            "ssm": jax.ShapeDtypeStruct(
                (cfg.n_layers, B, self.nh, s.state_dim, s.head_dim),
                jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                (cfg.n_layers, B, s.conv_width - 1, self.conv_ch), dt),
        }

    def init_cache(self, B, max_seq=0):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_struct(B, max_seq))

    def prefill(self, params, tokens, max_seq=None, remat: bool = True,
                prompt_lens=None):
        cfg = self.cfg
        x = cm.embed_tokens(params["embed"], tokens, self.compute_dtype)
        lens = None if prompt_lens is None \
            else jnp.asarray(prompt_lens, jnp.int32)

        def body(x, lp):
            h = cm.apply_norm(lp["norm"], x, cfg.norm)
            out, (hf, tail) = mamba_block(lp["mamba"], h, cfg,
                                          return_state=True, seq_lens=lens)
            return x + out, {"ssm": hf, "conv": tail}

        if remat:
            body = cm.remat(body)
        x, cache = lax.scan(body, x, params["layers"])
        last = x[:, -1:] if lens is None \
            else cm.gather_last_positions(x, lens)
        x = cm.apply_norm(params["final_norm"], last, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        return logits[:, 0], cache

    def cache_slot_axes(self):
        """Batch-axis index per cache leaf (for slot-wise admission)."""
        return {"ssm": 1, "conv": 1}

    def paged_cache_keys(self):
        """Constant-size recurrent state: nothing to page."""
        return []

    def cache_max_seq(self, cache) -> int:
        return 0    # constant-size state; no sequence capacity

    def prefill_into_slot(self, params, cache, tokens, slot):
        """Prefill one prompt (1, P) and install its SSM/conv state into
        ``slot`` of an existing slot-pool cache."""
        logits, sub = self.prefill(params, tokens, remat=False)
        return logits, cm.write_cache_slot(cache, sub, slot,
                                           self.cache_slot_axes())

    def decode_step(self, params, cache, tokens, pos, block_tables=None):
        # block_tables accepted for API uniformity; no paged leaves here
        cfg = self.cfg
        x = cm.embed_tokens(params["embed"], tokens[:, None],
                            self.compute_dtype)

        def body(x, inp):
            lp, ssm, conv = inp
            h = cm.apply_norm(lp["norm"], x, cfg.norm)
            out, (ssm, conv) = mamba_decode_step(lp["mamba"], h,
                                                 (ssm, conv), cfg)
            return x + out, {"ssm": ssm, "conv": conv}

        x, new_cache = lax.scan(body, x,
                                (params["layers"], cache["ssm"],
                                 cache["conv"]))
        x = cm.apply_norm(params["final_norm"], x, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        return logits[:, 0], new_cache

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32

        def sds(shp, dt=i32):
            return jax.ShapeDtypeStruct(tuple(shp), dt)

        if shape.kind == "train":
            return {"tokens": sds((B, S)), "targets": sds((B, S))}
        if shape.kind == "prefill":
            return {"tokens": sds((B, S))}
        return {"tokens": sds((B,)), "pos": sds((B,)),
                "cache": self._cache_struct(B, S)}
