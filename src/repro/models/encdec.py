"""Encoder–decoder transformer (seamless-m4t backbone).

The speech/text frontend is a stub per the assignment: the encoder consumes
precomputed frame embeddings (B, F, d).  Encoder: bidirectional self-attn;
decoder: causal self-attn + cross-attn to the encoder memory.  Decode keeps
a self-attn KV cache plus precomputed cross-attn K/V.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig, ShapeConfig
from . import common as cm
from .common import ParamBuilder, Params
from .transformer import _stack_tree


class EncDecLM:
    def __init__(self, cfg: ModelConfig, block_k: int = 1024):
        self.cfg = cfg
        self.block_k = block_k
        self.head_dim = cfg.resolved_head_dim
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    # -- params -----------------------------------------------------------
    def _enc_layer(self, b: ParamBuilder) -> Params:
        cfg = self.cfg
        return {
            "norm_attn": cm.init_norm(b, cfg.d_model, cfg.norm),
            "attn": cm.init_attention(b, cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, self.head_dim),
            "norm_mlp": cm.init_norm(b, cfg.d_model, cfg.norm),
            "mlp": cm.init_mlp(b, cfg.d_model, cfg.d_ff, cfg.activation),
        }

    def _dec_layer(self, b: ParamBuilder) -> Params:
        p = self._enc_layer(b)
        cfg = self.cfg
        p["norm_cross"] = cm.init_norm(b, cfg.d_model, cfg.norm)
        p["cross"] = cm.init_attention(b, cfg.d_model, cfg.n_heads,
                                       cfg.n_kv_heads, self.head_dim)
        return p

    def _build(self, mode, rng=None):
        cfg = self.cfg
        b = ParamBuilder(mode, rng, dtype=self.param_dtype)
        params = {
            "embed": cm.init_embedding(b, cfg.vocab_size, cfg.d_model,
                                       cfg.tie_embeddings,
                                       max_seq=cfg.max_train_seq,
                                       learned_pos=True),
            "enc_pos": b.param((cfg.encoder_frontend_len, cfg.d_model),
                               (None, "embed"), scale=0.02),
            "enc_final_norm": cm.init_norm(b, cfg.d_model, cfg.norm),
            "final_norm": cm.init_norm(b, cfg.d_model, cfg.norm),
        }
        if mode == ParamBuilder.INIT:
            enc = [self._enc_layer(b) for _ in range(cfg.n_encoder_layers)]
            dec = [self._dec_layer(b) for _ in range(cfg.n_layers)]
            params["enc_layers"] = jax.tree.map(lambda *x: jnp.stack(x), *enc)
            params["dec_layers"] = jax.tree.map(lambda *x: jnp.stack(x), *dec)
        else:
            params["enc_layers"] = _stack_tree(self._enc_layer(b),
                                               cfg.n_encoder_layers, mode)
            params["dec_layers"] = _stack_tree(self._dec_layer(b),
                                               cfg.n_layers, mode)
        return params

    def init(self, rng):
        return self._build(ParamBuilder.INIT, rng)

    def abstract_params(self):
        return self._build(ParamBuilder.ABSTRACT)

    def param_axes(self):
        return self._build(ParamBuilder.AXES)

    # -- encoder ------------------------------------------------------------
    def encode(self, params, frames, remat: bool = True):
        """frames: (B, F, d) stubbed frontend embeddings."""
        cfg = self.cfg
        F = frames.shape[1]
        x = frames.astype(self.compute_dtype) \
            + params["enc_pos"][:F].astype(self.compute_dtype)

        def body(x, lp):
            h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
            h = cm.attention_block(lp["attn"], h, cfg_theta=0.0,
                                   positional="learned", causal=False,
                                   block_k=self.block_k)
            x = x + h
            h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
            x = x + cm.apply_mlp(lp["mlp"], h, cfg.activation)
            return x, None

        if remat:
            body = cm.remat(body)
        x, _ = lax.scan(body, x, params["enc_layers"])
        return cm.apply_norm(params["enc_final_norm"], x, cfg.norm)

    # -- decoder ------------------------------------------------------------
    def _dec_body(self, lp, x, memory, q_offset=0):
        cfg = self.cfg
        h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
        h = cm.attention_block(lp["attn"], h, cfg_theta=0.0,
                               positional="learned", causal=True,
                               q_offset=q_offset, block_k=self.block_k)
        x = x + h
        h = cm.apply_norm(lp["norm_cross"], x, cfg.norm)
        h = cm.attention_block(lp["cross"], h, cfg_theta=0.0,
                               positional="learned", causal=False,
                               kv_x=memory, block_k=self.block_k)
        x = x + h
        h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
        return x + cm.apply_mlp(lp["mlp"], h, cfg.activation)

    def loss(self, params, batch, rng=None, remat: bool = True):
        cfg = self.cfg
        memory = self.encode(params, batch["frames"], remat=remat)
        x = cm.embed_tokens(params["embed"], batch["tokens"],
                            self.compute_dtype)

        def body(x, lp):
            return self._dec_body(lp, x, memory), None

        if remat:
            body = cm.remat(body)
        x, _ = lax.scan(body, x, params["dec_layers"])
        x = cm.apply_norm(params["final_norm"], x, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        loss = cm.softmax_cross_entropy(logits, batch["targets"],
                                        batch.get("mask"), z_loss=1e-4)
        return loss, {"loss": loss, "ce_loss": loss}

    # -- serving ------------------------------------------------------------
    def _cache_struct(self, B, max_seq):
        cfg = self.cfg
        KV, D = cfg.n_kv_heads, self.head_dim
        L = cfg.n_layers
        F = cfg.encoder_frontend_len
        dt = self.compute_dtype

        def sds(shape):
            return jax.ShapeDtypeStruct(tuple(shape), dt)

        return {"k": sds((L, B, max_seq, KV, D)),
                "v": sds((L, B, max_seq, KV, D)),
                "cross_k": sds((L, B, F, KV, D)),
                "cross_v": sds((L, B, F, KV, D))}

    def init_cache(self, B, max_seq):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_struct(B, max_seq))

    def prefill(self, params, tokens, frames=None, max_seq=None,
                remat: bool = True, prompt_lens=None):
        """Encode frames, run decoder over prompt tokens, build caches.

        ``prompt_lens`` (B,) supports right-padded batched prefill: padded
        self-attention keys are masked and the logits are gathered at each
        row's last valid position (cross-attention is per-query, so padded
        rows only corrupt their own unused outputs).
        """
        cfg = self.cfg
        memory = self.encode(params, frames, remat=remat)
        x = cm.embed_tokens(params["embed"], tokens, self.compute_dtype)
        B, S = x.shape[0], x.shape[1]
        max_seq = max_seq or S
        lens = None if prompt_lens is None \
            else jnp.asarray(prompt_lens, jnp.int32)

        def body(x, lp):
            h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
            h, (k, v) = cm.attention_block(
                lp["attn"], h, cfg_theta=0.0, positional="learned",
                causal=True, block_k=self.block_k, return_kv=True,
                kv_valid_len=lens)
            x = x + h
            h = cm.apply_norm(lp["norm_cross"], x, cfg.norm)
            h, (ck, cv) = cm.attention_block(
                lp["cross"], h, cfg_theta=0.0, positional="learned",
                causal=False, kv_x=memory, block_k=self.block_k,
                return_kv=True)
            x = x + h
            h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
            x = x + cm.apply_mlp(lp["mlp"], h, cfg.activation)
            kpad = jnp.zeros((B, max_seq) + k.shape[2:], k.dtype)
            cache = {"k": lax.dynamic_update_slice(kpad, k, (0, 0, 0, 0)),
                     "v": lax.dynamic_update_slice(jnp.zeros_like(kpad), v,
                                                   (0, 0, 0, 0)),
                     "cross_k": ck, "cross_v": cv}
            return x, cache

        if remat:
            body = cm.remat(body)
        x, cache = lax.scan(body, x, params["dec_layers"])
        last = x[:, -1:] if lens is None \
            else cm.gather_last_positions(x, lens)
        x = cm.apply_norm(params["final_norm"], last, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        return logits[:, 0], cache

    def cache_slot_axes(self):
        """Batch-axis index per cache leaf (for slot-wise admission)."""
        return {"k": 1, "v": 1, "cross_k": 1, "cross_v": 1}

    def paged_cache_keys(self):
        """Self-attention KV grows with max_seq -> paged; cross K/V is a
        fixed F-length block per slot -> dense."""
        return ["k", "v"]

    def cache_max_seq(self, cache) -> int:
        return cache["k"].shape[2]

    def prefill_into_slot(self, params, cache, tokens, slot, frames=None):
        """Prefill one (frames, prompt) pair and install its self- and
        cross-attention caches into ``slot`` of an existing pool cache."""
        logits, sub = self.prefill(params, tokens, frames=frames,
                                   max_seq=self.cache_max_seq(cache),
                                   remat=False)
        return logits, cm.write_cache_slot(cache, sub, slot,
                                           self.cache_slot_axes())

    def decode_step(self, params, cache, tokens, pos, block_tables=None):
        cfg = self.cfg
        B = tokens.shape[0]
        x = (jnp.take(params["embed"]["wte"], tokens[:, None], axis=0)
             + jnp.take(params["embed"]["wpe"], pos[:, None], axis=0)
             ).astype(self.compute_dtype)
        ar = jnp.arange(B)

        def body(x, inp):
            lp, c = inp
            h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
            q = jnp.einsum("bsd,dhk->bshk", h,
                           cm.cast(lp["attn"]["wq"], h.dtype))
            k = jnp.einsum("bsd,dhk->bshk", h,
                           cm.cast(lp["attn"]["wk"], h.dtype))
            v = jnp.einsum("bsd,dhk->bshk", h,
                           cm.cast(lp["attn"]["wv"], h.dtype))
            if block_tables is not None:
                ks, vs = c.get("k_scale"), c.get("v_scale")
                if ks is not None:
                    kc, ks = cm.paged_cache_write_quant(
                        c["k"], ks, k[:, 0], block_tables, pos)
                    vc, vs = cm.paged_cache_write_quant(
                        c["v"], vs, v[:, 0], block_tables, pos)
                else:
                    kc = cm.paged_cache_write(c["k"], k[:, 0],
                                              block_tables, pos)
                    vc = cm.paged_cache_write(c["v"], v[:, 0],
                                              block_tables, pos)
                o = cm.paged_decode_attention(q, kc, vc, block_tables,
                                              pos=pos, k_scales=ks,
                                              v_scales=vs)
            else:
                ks = vs = None
                kc = c["k"].at[ar, pos].set(k[:, 0])
                vc = c["v"].at[ar, pos].set(v[:, 0])
                o = cm.decode_attention(q, kc, vc, pos=pos)
            x = x + jnp.einsum("bshk,hkd->bsd", o,
                               cm.cast(lp["attn"]["wo"], h.dtype))
            h = cm.apply_norm(lp["norm_cross"], x, cfg.norm)
            q = jnp.einsum("bsd,dhk->bshk", h,
                           cm.cast(lp["cross"]["wq"], h.dtype))
            F = c["cross_k"].shape[1]
            o = cm.decode_attention(q, c["cross_k"], c["cross_v"],
                                    pos=jnp.full((B,), F - 1, jnp.int32))
            x = x + jnp.einsum("bshk,hkd->bsd", o,
                               cm.cast(lp["cross"]["wo"], h.dtype))
            h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
            x = x + cm.apply_mlp(lp["mlp"], h, cfg.activation)
            nc = {"k": kc, "v": vc, "cross_k": c["cross_k"],
                  "cross_v": c["cross_v"]}
            if ks is not None:
                nc["k_scale"], nc["v_scale"] = ks, vs
            return x, nc

        x, new_cache = lax.scan(body, x, (params["dec_layers"], cache))
        x = cm.apply_norm(params["final_norm"], x, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        return logits[:, 0], new_cache

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        F = cfg.encoder_frontend_len
        i32 = jnp.int32

        def sds(shp, dt=i32):
            return jax.ShapeDtypeStruct(tuple(shp), dt)

        frames = sds((B, F, cfg.d_model), self.compute_dtype)
        if shape.kind == "train":
            return {"tokens": sds((B, S)), "targets": sds((B, S)),
                    "frames": frames}
        if shape.kind == "prefill":
            return {"tokens": sds((B, S)), "frames": frames}
        return {"tokens": sds((B,)), "pos": sds((B,)),
                "cache": self._cache_struct(B, S)}
