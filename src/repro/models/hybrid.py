"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block.

81 Mamba2 blocks; a single shared transformer block (attention + MLP whose
weights are reused at every application) runs every ``attn_every`` blocks on
``concat(hidden, embedding)`` (2·d_model), projecting back to d_model
(arXiv:2411.15242).  Weights are shared; KV caches are per-application.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig, ShapeConfig
from . import common as cm
from .common import ParamBuilder, Params
from .ssm import (init_mamba_block, mamba_block, mamba_decode_step)
from .transformer import _stack_tree


class HybridLM:
    def __init__(self, cfg: ModelConfig, block_k: int = 1024):
        self.cfg = cfg
        self.block_k = block_k
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        s = cfg.ssm
        self.d_inner = s.expand * cfg.d_model
        self.nh = self.d_inner // s.head_dim
        self.conv_ch = self.d_inner + 2 * s.n_groups * s.state_dim
        per = cfg.attn_every
        self.n_groups = cfg.n_layers // per          # full groups
        self.tail = cfg.n_layers % per               # leftover mamba layers
        # shared attention runs before each group and once before the tail
        self.n_attn = self.n_groups + (1 if self.tail else 0)
        self.attn_d = 2 * cfg.d_model
        assert self.attn_d % cfg.n_heads == 0
        self.attn_head_dim = self.attn_d // cfg.n_heads

    # -- params -----------------------------------------------------------
    def _shared_block(self, b: ParamBuilder) -> Params:
        cfg = self.cfg
        return {
            "norm_attn": cm.init_norm(b, self.attn_d, "rms"),
            "attn": cm.init_attention(b, self.attn_d, cfg.n_heads,
                                      cfg.n_kv_heads, self.attn_head_dim,
                                      d_out=cfg.d_model),
            "norm_mlp": cm.init_norm(b, self.attn_d, "rms"),
            "mlp": {
                "w_up": b.param((self.attn_d, cfg.d_ff), ("embed", "mlp")),
                "w_down": b.param((cfg.d_ff, cfg.d_model), ("mlp", "embed")),
            },
        }

    def _build(self, mode, rng=None):
        cfg = self.cfg
        b = ParamBuilder(mode, rng, dtype=self.param_dtype)
        params = {
            "embed": cm.init_embedding(b, cfg.vocab_size, cfg.d_model,
                                       cfg.tie_embeddings),
            "shared": self._shared_block(b),
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
            params["layers"] = _stack_tree(layer(b), cfg.n_layers, mode)
        return params

    def init(self, rng):
        return self._build(ParamBuilder.INIT, rng)

    def abstract_params(self):
        return self._build(ParamBuilder.ABSTRACT)

    def param_axes(self):
        return self._build(ParamBuilder.AXES)

    # -- shared attention block (full-sequence) -----------------------------
    def _shared_fwd(self, sp: Params, h, emb, return_kv=False,
                    kv_valid_len=None):
        cfg = self.cfg
        u = jnp.concatenate([h, emb], axis=-1)
        un = cm.apply_norm(sp["norm_attn"], u, "rms")
        res = cm.attention_block(
            sp["attn"], un, cfg_theta=cfg.rope_theta, positional="rope",
            causal=True, block_k=self.block_k, return_kv=return_kv,
            kv_valid_len=kv_valid_len)
        if return_kv:
            attn_out, kv = res
        else:
            attn_out, kv = res, None
        h = h + attn_out
        u = jnp.concatenate([h, emb], axis=-1)
        un = cm.apply_norm(sp["norm_mlp"], u, "rms")
        ff = jnp.einsum("bsd,df->bsf", un, cm.cast(sp["mlp"]["w_up"],
                                                   un.dtype))
        ff = jax.nn.gelu(ff, approximate=True)
        h = h + jnp.einsum("bsf,fd->bsd", ff, cm.cast(sp["mlp"]["w_down"],
                                                      un.dtype))
        return (h, kv) if return_kv else h

    def _shared_decode(self, sp: Params, h, emb, kc, vc, pos,
                       block_tables=None, ks=None, vs=None):
        cfg = self.cfg
        B = h.shape[0]
        u = jnp.concatenate([h, emb], axis=-1)
        un = cm.apply_norm(sp["norm_attn"], u, "rms")
        q = jnp.einsum("bsd,dhk->bshk", un, cm.cast(sp["attn"]["wq"],
                                                    un.dtype))
        k = jnp.einsum("bsd,dhk->bshk", un, cm.cast(sp["attn"]["wk"],
                                                    un.dtype))
        v = jnp.einsum("bsd,dhk->bshk", un, cm.cast(sp["attn"]["wv"],
                                                    un.dtype))
        q = cm.apply_rope(q, pos[:, None], cfg.rope_theta)
        k = cm.apply_rope(k, pos[:, None], cfg.rope_theta)
        if block_tables is not None:
            if ks is not None:
                kc, ks = cm.paged_cache_write_quant(kc, ks, k[:, 0],
                                                    block_tables, pos)
                vc, vs = cm.paged_cache_write_quant(vc, vs, v[:, 0],
                                                    block_tables, pos)
            else:
                kc = cm.paged_cache_write(kc, k[:, 0], block_tables, pos)
                vc = cm.paged_cache_write(vc, v[:, 0], block_tables, pos)
            o = cm.paged_decode_attention(q, kc, vc, block_tables, pos=pos,
                                          k_scales=ks, v_scales=vs)
        else:
            ar = jnp.arange(B)
            kc = kc.at[ar, pos].set(k[:, 0])
            vc = vc.at[ar, pos].set(v[:, 0])
            o = cm.decode_attention(q, kc, vc, pos=pos)
        h = h + jnp.einsum("bshk,hkd->bsd", o, cm.cast(sp["attn"]["wo"],
                                                       un.dtype))
        u = jnp.concatenate([h, emb], axis=-1)
        un = cm.apply_norm(sp["norm_mlp"], u, "rms")
        ff = jax.nn.gelu(jnp.einsum("bsd,df->bsf", un,
                                    cm.cast(sp["mlp"]["w_up"], un.dtype)),
                         approximate=True)
        h = h + jnp.einsum("bsf,fd->bsd", ff,
                           cm.cast(sp["mlp"]["w_down"], un.dtype))
        return h, kc, vc, ks, vs

    # -- training ----------------------------------------------------------
    def forward_hidden(self, params, x, remat: bool = True):
        cfg = self.cfg
        per = cfg.attn_every
        emb = x
        shared = params["shared"]
        n_scan = self.n_groups * per
        glayers = jax.tree.map(
            lambda a: a[:n_scan].reshape((self.n_groups, per) + a.shape[1:]),
            params["layers"])

        def group_body(x, gp):
            x = self._shared_fwd(shared, x, emb)
            for i in range(per):
                lp = jax.tree.map(lambda a, i=i: a[i], gp)
                h = cm.apply_norm(lp["norm"], x, cfg.norm)
                x = x + mamba_block(lp["mamba"], h, cfg)
            return x, None

        body = cm.remat(group_body) if remat else group_body
        x, _ = lax.scan(body, x, glayers)
        if self.tail:
            x = self._shared_fwd(shared, x, emb)
            for i in range(n_scan, cfg.n_layers):
                lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
                h = cm.apply_norm(lp["norm"], x, cfg.norm)
                x = x + mamba_block(lp["mamba"], h, cfg)
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

    # -- serving ------------------------------------------------------------
    def _cache_struct(self, B, max_seq):
        cfg = self.cfg
        s = cfg.ssm
        dt = self.compute_dtype
        KV, D = cfg.n_kv_heads, self.attn_head_dim

        def sds(shape, dtype=dt):
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

        return {
            "ssm": sds((cfg.n_layers, B, self.nh, s.state_dim, s.head_dim),
                       jnp.float32),
            "conv": sds((cfg.n_layers, B, s.conv_width - 1, self.conv_ch)),
            "k": sds((self.n_attn, B, max_seq, KV, D)),
            "v": sds((self.n_attn, B, max_seq, KV, D)),
        }

    def init_cache(self, B, max_seq):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_struct(B, max_seq))

    def prefill(self, params, tokens, max_seq=None, remat: bool = True,
                prompt_lens=None):
        cfg = self.cfg
        per = cfg.attn_every
        x = cm.embed_tokens(params["embed"], tokens, self.compute_dtype)
        B, S = x.shape[0], x.shape[1]
        max_seq = max_seq or S
        lens = None if prompt_lens is None \
            else jnp.asarray(prompt_lens, jnp.int32)
        emb = x
        shared = params["shared"]
        n_scan = self.n_groups * per
        glayers = jax.tree.map(
            lambda a: a[:n_scan].reshape((self.n_groups, per) + a.shape[1:]),
            params["layers"])

        def pad_kv(k):
            kpad = jnp.zeros((B, max_seq) + k.shape[2:], k.dtype)
            return lax.dynamic_update_slice(kpad, k, (0, 0, 0, 0))

        def group_body(x, gp):
            x, (k, v) = self._shared_fwd(shared, x, emb, return_kv=True,
                                         kv_valid_len=lens)
            cache = {"k": pad_kv(k), "v": pad_kv(v), "ssm": [], "conv": []}
            for i in range(per):
                lp = jax.tree.map(lambda a, i=i: a[i], gp)
                h = cm.apply_norm(lp["norm"], x, cfg.norm)
                out, (hf, tail) = mamba_block(lp["mamba"], h, cfg,
                                              return_state=True,
                                              seq_lens=lens)
                x = x + out
                cache["ssm"].append(hf)
                cache["conv"].append(tail)
            cache["ssm"] = jnp.stack(cache["ssm"])
            cache["conv"] = jnp.stack(cache["conv"])
            return x, cache

        body = cm.remat(group_body) if remat else group_body
        x, cache = lax.scan(body, x, glayers)
        cache = {"ssm": cache["ssm"].reshape((n_scan,) +
                                             cache["ssm"].shape[2:]),
                 "conv": cache["conv"].reshape((n_scan,) +
                                               cache["conv"].shape[2:]),
                 "k": cache["k"], "v": cache["v"]}
        if self.tail:
            x, (k, v) = self._shared_fwd(shared, x, emb, return_kv=True,
                                         kv_valid_len=lens)
            cache["k"] = jnp.concatenate([cache["k"], pad_kv(k)[None]])
            cache["v"] = jnp.concatenate([cache["v"], pad_kv(v)[None]])
            ssm_t, conv_t = [], []
            for i in range(n_scan, cfg.n_layers):
                lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
                h = cm.apply_norm(lp["norm"], x, cfg.norm)
                out, (hf, tail) = mamba_block(lp["mamba"], h, cfg,
                                              return_state=True,
                                              seq_lens=lens)
                x = x + out
                ssm_t.append(hf)
                conv_t.append(tail)
            cache["ssm"] = jnp.concatenate([cache["ssm"], jnp.stack(ssm_t)])
            cache["conv"] = jnp.concatenate([cache["conv"],
                                             jnp.stack(conv_t)])
        last = x[:, -1:] if lens is None \
            else cm.gather_last_positions(x, lens)
        x = cm.apply_norm(params["final_norm"], last, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        return logits[:, 0], cache

    def cache_slot_axes(self):
        """Batch-axis index per cache leaf (for slot-wise admission)."""
        return {"ssm": 1, "conv": 1, "k": 1, "v": 1}

    def paged_cache_keys(self):
        """Shared-attention KV grows with max_seq -> paged; SSM/conv state
        is constant-size per slot -> dense."""
        return ["k", "v"]

    def cache_max_seq(self, cache) -> int:
        return cache["k"].shape[2]

    def prefill_into_slot(self, params, cache, tokens, slot):
        """Prefill one prompt (1, P) and install its SSM state + shared-
        attention KV into ``slot`` of an existing slot-pool cache."""
        logits, sub = self.prefill(params, tokens,
                                   max_seq=self.cache_max_seq(cache),
                                   remat=False)
        return logits, cm.write_cache_slot(cache, sub, slot,
                                           self.cache_slot_axes())

    def decode_step(self, params, cache, tokens, pos, block_tables=None):
        cfg = self.cfg
        per = cfg.attn_every
        x = cm.embed_tokens(params["embed"], tokens[:, None],
                            self.compute_dtype)
        emb = x
        shared = params["shared"]
        n_scan = self.n_groups * per
        glayers = jax.tree.map(
            lambda a: a[:n_scan].reshape((self.n_groups, per) + a.shape[1:]),
            params["layers"])
        quant = "k_scale" in cache
        gcaches = {
            "ssm": cache["ssm"][:n_scan].reshape(
                (self.n_groups, per) + cache["ssm"].shape[1:]),
            "conv": cache["conv"][:n_scan].reshape(
                (self.n_groups, per) + cache["conv"].shape[1:]),
            "k": cache["k"][:self.n_groups],
            "v": cache["v"][:self.n_groups],
        }
        if quant:
            gcaches["k_scale"] = cache["k_scale"][:self.n_groups]
            gcaches["v_scale"] = cache["v_scale"][:self.n_groups]

        def group_body(x, inp):
            gp, gc = inp
            x, kc, vc, ks, vs = self._shared_decode(
                shared, x, emb, gc["k"], gc["v"], pos,
                block_tables=block_tables, ks=gc.get("k_scale"),
                vs=gc.get("v_scale"))
            new = {"k": kc, "v": vc, "ssm": [], "conv": []}
            if ks is not None:
                new["k_scale"], new["v_scale"] = ks, vs
            for i in range(per):
                lp = jax.tree.map(lambda a, i=i: a[i], gp)
                h = cm.apply_norm(lp["norm"], x, cfg.norm)
                out, st = mamba_decode_step(
                    lp["mamba"], h, (gc["ssm"][i], gc["conv"][i]), cfg)
                x = x + out
                new["ssm"].append(st[0])
                new["conv"].append(st[1])
            new["ssm"] = jnp.stack(new["ssm"])
            new["conv"] = jnp.stack(new["conv"])
            return x, new

        x, new_cache = lax.scan(group_body, x, (glayers, gcaches))
        out_cache = {
            "ssm": new_cache["ssm"].reshape((n_scan,) +
                                            new_cache["ssm"].shape[2:]),
            "conv": new_cache["conv"].reshape((n_scan,) +
                                              new_cache["conv"].shape[2:]),
            "k": new_cache["k"], "v": new_cache["v"],
        }
        if quant:
            out_cache["k_scale"] = new_cache["k_scale"]
            out_cache["v_scale"] = new_cache["v_scale"]
        if self.tail:
            x, kc, vc, ks, vs = self._shared_decode(
                shared, x, emb, cache["k"][self.n_groups],
                cache["v"][self.n_groups], pos,
                block_tables=block_tables,
                ks=cache["k_scale"][self.n_groups] if quant else None,
                vs=cache["v_scale"][self.n_groups] if quant else None)
            out_cache["k"] = jnp.concatenate([out_cache["k"], kc[None]])
            out_cache["v"] = jnp.concatenate([out_cache["v"], vc[None]])
            if quant:
                out_cache["k_scale"] = jnp.concatenate(
                    [out_cache["k_scale"], ks[None]])
                out_cache["v_scale"] = jnp.concatenate(
                    [out_cache["v_scale"], vs[None]])
            ssm_t, conv_t = [], []
            for i in range(n_scan, cfg.n_layers):
                lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
                h = cm.apply_norm(lp["norm"], x, cfg.norm)
                out, st = mamba_decode_step(
                    lp["mamba"], h, (cache["ssm"][i], cache["conv"][i]), cfg)
                x = x + out
                ssm_t.append(st[0])
                conv_t.append(st[1])
            out_cache["ssm"] = jnp.concatenate([out_cache["ssm"],
                                                jnp.stack(ssm_t)])
            out_cache["conv"] = jnp.concatenate([out_cache["conv"],
                                                 jnp.stack(conv_t)])
        x = cm.apply_norm(params["final_norm"], x, cfg.norm)
        logits = cm.unembed(params["embed"], x)
        return logits[:, 0], out_cache

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
