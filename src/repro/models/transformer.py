"""Decoder-only transformer LM (dense, MoE, VLM) with scan-over-layers.

One implementation covers seven of the assigned archs:
  dense : llama3.2-1b/3b, yi-34b, nemotron-4-340b (relu^2/layernorm), gpt3-xl
  moe   : granite-moe-1b-a400m (32e top-8), llama4-scout (16e top-1 + shared
          expert + chunked local attention with a global layer every 4th)
  vlm   : internvl2-1b (stub patch embeddings prefixed to the sequence)

Layers are stacked (leading ``layers`` dim) and executed with ``lax.scan``;
for window/global alternation the scan runs over *groups* of
``global_attn_every`` layers so local layers keep ring-buffer KV caches
(sub-quadratic long-context decode) while every 4th layer stays global.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig, ShapeConfig
from ..obs.scopes import scope
from . import common as cm
from .common import ParamBuilder, Params


import os

# Sequence-parallel residual carry: measured *harmful* under XLA SPMD
# propagation (per-einsum seq re-gathers; see EXPERIMENTS.md §Perf A-2,
# refuted hypothesis) — off by default, kept for re-evaluation on TPU.
_SP_RESIDUAL = os.environ.get("REPRO_SP_RESIDUAL", "0") == "1"


def _stack_tree(tree, n: int, mode: str):
    """Add a leading layer dim of size n to every leaf (per builder mode)."""
    if mode == ParamBuilder.AXES:
        return jax.tree.map(lambda axes: ("layers",) + axes, tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)


class DecoderLM:
    """Functional decoder LM implementing the repro Model API."""

    def __init__(self, cfg: ModelConfig, block_k: int = 1024):
        self.cfg = cfg
        self.block_k = block_k
        self.head_dim = cfg.resolved_head_dim
        # layer grouping for local/global attention alternation
        if cfg.attn_window and cfg.global_attn_every:
            self.group = cfg.global_attn_every
            assert cfg.n_layers % self.group == 0, cfg.name
        else:
            self.group = 1
        # leading dense layers run before the scan, outside it
        self.n_groups = (cfg.n_layers - cfg.n_dense_layers) // self.group
        # dropless routed experts (apply_routed_moe) in the scanned layers
        self.routed = cfg.is_moe and cfg.moe.dropless
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)

    # -- layer flags ----------------------------------------------------
    def _layer_window(self, idx_in_group: int) -> int:
        """Static attention window for a layer (0 = full/global)."""
        cfg = self.cfg
        if not cfg.attn_window:
            return 0
        is_global = (idx_in_group == self.group - 1)
        return 0 if is_global else cfg.attn_window

    # -- params ----------------------------------------------------------
    def _init_layer(self, b: ParamBuilder, dense: bool = False) -> Params:
        cfg = self.cfg
        p: Params = {
            "norm_attn": cm.init_norm(b, cfg.d_model, cfg.norm),
            "attn": (cm.init_mla(b, cfg.d_model, cfg.n_heads, cfg.mla)
                     if cfg.mla.enabled else
                     cm.init_attention(b, cfg.d_model, cfg.n_heads,
                                       cfg.n_kv_heads, self.head_dim)),
            "norm_mlp": cm.init_norm(b, cfg.d_model, cfg.norm),
        }
        if self.routed and not dense:
            p["moe"] = cm.init_routed_moe(b, cfg.d_model, cfg.d_ff, cfg.moe)
        elif cfg.is_moe and not dense:
            p["moe"] = cm.init_moe(b, cfg.d_model, cfg.d_ff,
                                   cfg.moe.n_experts, cfg.activation,
                                   cfg.moe.shared_expert)
        else:
            p["mlp"] = cm.init_mlp(b, cfg.d_model, cfg.d_ff, cfg.activation)
        return p

    def _build(self, mode: str, rng=None) -> Params:
        cfg = self.cfg
        b = ParamBuilder(mode, rng, dtype=self.param_dtype)
        params: Params = {
            "embed": cm.init_embedding(
                b, cfg.vocab_size, cfg.d_model, cfg.tie_embeddings,
                max_seq=cfg.max_train_seq,
                learned_pos=(cfg.positional == "learned")),
            "final_norm": cm.init_norm(b, cfg.d_model, cfg.norm),
        }
        n_scan = cfg.n_layers - cfg.n_dense_layers
        for key, n, dense in (("dense_layers", cfg.n_dense_layers, True),
                              ("layers", n_scan, False)):
            if not n:
                continue
            if mode == ParamBuilder.INIT:
                layers = [self._init_layer(b, dense) for _ in range(n)]
                params[key] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *layers)
            else:
                params[key] = _stack_tree(self._init_layer(b, dense), n,
                                          mode)
        return params

    def frozen_mask(self):
        """Per parameter, True for state that the optimizer leaves alone
        (the router's selection bias, moved by :meth:`update_frozen`);
        None when the model has no such state."""
        if not (self.routed and self.cfg.moe.bias_rate):
            return None
        return jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key == "select_bias",
            self.abstract_params())

    def update_frozen(self, params: Params, metrics: Dict) -> Params:
        """The selection bias after a step: each expert's moves by
        ``bias_rate`` toward the mean load, ``b += rate * sign(mean - load)``
        (DeepSeek-V3), with the load the step's assignments on this chip."""
        if self.frozen_mask() is None:
            return params
        load = metrics["expert_load"]                      # (layers, E)
        step = self.cfg.moe.bias_rate * jnp.sign(
            load.mean(-1, keepdims=True) - load)
        moe = dict(params["layers"]["moe"])
        moe["select_bias"] = moe["select_bias"] + step.astype(
            moe["select_bias"].dtype)
        return {**params, "layers": {**params["layers"], "moe": moe}}

    def init(self, rng) -> Params:
        return self._build(ParamBuilder.INIT, rng)

    def abstract_params(self) -> Params:
        return self._build(ParamBuilder.ABSTRACT)

    def param_axes(self) -> Params:
        return self._build(ParamBuilder.AXES)

    # -- forward ----------------------------------------------------------
    def _layer_fwd(self, lp: Params, x, idx_in_group: int, q_offset: int,
                   aux_acc: Dict):
        cfg = self.cfg
        window = self._layer_window(idx_in_group)
        h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
        if cfg.mla.enabled:
            h = cm.mla_attention_block(
                lp["attn"], h, n_heads=cfg.n_heads, mla=cfg.mla,
                theta=cfg.rope_theta, block_k=self.block_k)
        else:
            h = cm.attention_block(
                lp["attn"], h, cfg_theta=cfg.rope_theta,
                positional=cfg.positional, causal=True, window=window,
                softcap=cfg.attn_logit_softcap, q_offset=q_offset,
                block_k=self.block_k)
        x = x + h
        h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
        if "mlp" in lp:
            h = cm.apply_mlp(lp["mlp"], h, cfg.activation)
        elif self.routed:
            h, aux = cm.apply_routed_moe(
                lp["moe"], h, n_experts=cfg.moe.n_experts,
                top_k=cfg.moe.top_k, routed_scale=cfg.moe.routed_scale)
            aux_acc.update(aux)
        elif cfg.is_moe:
            h, aux = cm.apply_moe(
                lp["moe"], h, n_experts=cfg.moe.n_experts,
                top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor,
                activation=cfg.activation,
                shared_expert=cfg.moe.shared_expert)
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0.0) + v
        return x + h, aux_acc

    def forward_hidden(self, params: Params, x: jnp.ndarray,
                       q_offset: int = 0, remat: bool = True
                       ) -> Tuple[jnp.ndarray, Dict]:
        """Run the layer stack on embedded input x: (B, S, d)."""
        cfg = self.cfg
        for i in range(cfg.n_dense_layers):
            dense = functools.partial(self._layer_fwd, idx_in_group=0,
                                      q_offset=q_offset, aux_acc={})
            if remat:
                dense = cm.remat(dense)
            x, _ = dense(jax.tree.map(lambda a, i=i: a[i],
                                      params["dense_layers"]), x)
        glayers = jax.tree.map(
            lambda a: a.reshape((self.n_groups, self.group) + a.shape[1:]),
            params["layers"])

        def group_body(x, gp):
            aux: Dict[str, Any] = {}
            for i in range(self.group):
                lp = jax.tree.map(lambda a, i=i: a[i], gp)
                x, aux = self._layer_fwd(lp, x, i, q_offset, aux)
            if self.routed:
                return x, aux
            if _SP_RESIDUAL:
                # sequence-parallel residual stream: the scan carry (and
                # the per-layer saved residuals under remat) shard over
                # the model axis; attention re-gathers seq (Megatron-SP).
                x = cm.shard_hint(x, "batch", "model", None)
            aux_vec = jnp.stack(
                [jnp.asarray(aux.get(k, 0.0), jnp.float32)
                 for k in ("load_balance", "router_z", "dropped_frac")])
            return x, aux_vec

        body = cm.remat(group_body) if remat else group_body
        x, aux_stack = lax.scan(body, x, glayers)
        aux = {}
        if self.routed:
            # the balance loss and row counts as means over the MoE
            # layers; each layer's expert loads kept for the bias rule
            aux = {k: (v if k == "expert_load" else v.mean())
                   for k, v in aux_stack.items()}
        elif cfg.is_moe:
            s = aux_stack.sum(axis=0)
            aux = {"load_balance": s[0] / cfg.n_layers,
                   "router_z": s[1] / cfg.n_layers,
                   "dropped_frac": s[2] / cfg.n_layers}
        return x, aux

    def _embed_input(self, params, tokens, patch_embeds=None, pos_offset=0):
        x = cm.embed_tokens(params["embed"], tokens, self.compute_dtype,
                            pos_offset=pos_offset)
        if patch_embeds is not None:
            x = jnp.concatenate(
                [patch_embeds.astype(self.compute_dtype), x], axis=1)
        return x

    def logits(self, params, x):
        x = cm.apply_norm(params["final_norm"], x, self.cfg.norm)
        return cm.unembed(params["embed"], x)

    # -- training ----------------------------------------------------------
    def loss(self, params: Params, batch: Dict[str, jnp.ndarray],
             rng=None, remat: bool = True):
        cfg = self.cfg
        tokens, targets = batch["tokens"], batch["targets"]
        patch = batch.get("patch_embeds")
        x = self._embed_input(params, tokens, patch)
        x, aux = self.forward_hidden(params, x, remat=remat)
        if patch is not None:
            x = x[:, patch.shape[1]:]          # loss only over text positions
        logits = self.logits(params, x)
        mask = batch.get("mask")
        with scope("head"):
            loss = cm.softmax_cross_entropy(logits, targets, mask,
                                            z_loss=1e-4)
        metrics = {"ce_loss": loss}
        if self.routed:
            loss = loss + cfg.moe.aux_loss_weight * aux["balance"]
            metrics.update(aux)
        elif cfg.is_moe:
            loss = (loss + cfg.moe.aux_loss_weight * aux["load_balance"]
                    + cfg.moe.router_z_loss_weight * aux["router_z"])
            metrics.update(aux)
        metrics["loss"] = loss
        return loss, metrics

    # -- serving ----------------------------------------------------------
    def _cache_struct(self, B: int, max_seq: int):
        """Abstract KV-cache tree (grouped; ring buffers for local layers)."""
        cfg = self.cfg
        if cfg.mla.enabled or cfg.n_dense_layers or self.routed:
            raise NotImplementedError(
                f"{cfg.name}: serving latent attention, leading dense "
                f"layers and routed experts is not implemented; the model "
                f"trains only")
        KV, D = cfg.n_kv_heads, self.head_dim
        dt = self.compute_dtype

        def sds(shape):
            return jax.ShapeDtypeStruct(tuple(shape), dt)

        cache = {}
        if self.group == 1:
            cache["k"] = sds((self.n_groups, B, max_seq, KV, D))
            cache["v"] = sds((self.n_groups, B, max_seq, KV, D))
        else:
            W = min(cfg.attn_window, max_seq)
            cache["k_local"] = sds((self.n_groups, self.group - 1, B, W, KV, D))
            cache["v_local"] = sds((self.n_groups, self.group - 1, B, W, KV, D))
            cache["k_global"] = sds((self.n_groups, B, max_seq, KV, D))
            cache["v_global"] = sds((self.n_groups, B, max_seq, KV, D))
        return cache

    def init_cache(self, B: int, max_seq: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache_struct(B, max_seq))

    def prefill(self, params: Params, tokens: jnp.ndarray,
                patch_embeds=None, max_seq: Optional[int] = None,
                remat: bool = True,
                prompt_lens: Optional[jnp.ndarray] = None):
        """Process a prompt; return (last-position logits, filled cache).

        ``prompt_lens`` (B,) enables *batched bucketed* prefill: rows are
        right-padded to a shared bucket length; attention masks padded key
        positions, ring windows gather per-row valid tails, and the logits
        are taken at each row's last valid position.  Padded cache
        positions hold garbage, which decode masks by position.
        """
        cfg = self.cfg
        x = self._embed_input(params, tokens, patch_embeds)
        B, S = x.shape[0], x.shape[1]
        max_seq = max_seq or S
        cache = self.init_cache(B, max_seq)
        valid_len = None
        if prompt_lens is not None:
            P = 0 if patch_embeds is None else patch_embeds.shape[1]
            valid_len = jnp.asarray(prompt_lens, jnp.int32) + P
        glayers = jax.tree.map(
            lambda a: a.reshape((self.n_groups, self.group) + a.shape[1:]),
            params["layers"])

        def group_body(x, gp):
            new_cache = {}
            for i in range(self.group):
                lp = jax.tree.map(lambda a, i=i: a[i], gp)
                window = self._layer_window(i)
                h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
                h, (k, v) = cm.attention_block(
                    lp["attn"], h, cfg_theta=cfg.rope_theta,
                    positional=cfg.positional, causal=True, window=window,
                    softcap=cfg.attn_logit_softcap, block_k=self.block_k,
                    return_kv=True, kv_valid_len=valid_len)
                x = x + h
                h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
                if cfg.is_moe:
                    h, _ = cm.apply_moe(
                        lp["moe"], h, n_experts=cfg.moe.n_experts,
                        top_k=cfg.moe.top_k,
                        capacity_factor=cfg.moe.capacity_factor,
                        activation=cfg.activation,
                        shared_expert=cfg.moe.shared_expert, drop=False)
                else:
                    h = cm.apply_mlp(lp["mlp"], h, cfg.activation)
                x = x + h
                if self.group == 1:
                    kpad = jnp.zeros((B, max_seq) + k.shape[2:], k.dtype)
                    new_cache["k"] = lax.dynamic_update_slice(
                        kpad, k, (0, 0, 0, 0))
                    new_cache["v"] = lax.dynamic_update_slice(
                        jnp.zeros_like(kpad), v, (0, 0, 0, 0))
                else:
                    W = min(cfg.attn_window, max_seq)
                    if window:  # local layer: keep last W, ring-indexed
                        # slot (p % W) holds position p, per-row valid tail
                        lens = valid_len if valid_len is not None \
                            else jnp.full((B,), S, jnp.int32)
                        kw = cm.gather_ring_window(k, lens, W)
                        vw = cm.gather_ring_window(v, lens, W)
                        new_cache.setdefault("k_local", []).append(kw)
                        new_cache.setdefault("v_local", []).append(vw)
                    else:
                        kpad = jnp.zeros((B, max_seq) + k.shape[2:], k.dtype)
                        new_cache["k_global"] = lax.dynamic_update_slice(
                            kpad, k, (0, 0, 0, 0))
                        new_cache["v_global"] = lax.dynamic_update_slice(
                            jnp.zeros_like(kpad), v, (0, 0, 0, 0))
            for key in ("k_local", "v_local"):
                if key in new_cache:
                    new_cache[key] = jnp.stack(new_cache[key])
            return x, new_cache

        if remat:
            group_body = cm.remat(group_body)
        x, cache = lax.scan(group_body, x, glayers)
        last = x[:, -1:] if valid_len is None \
            else cm.gather_last_positions(x, valid_len)
        logits = self.logits(params, last)
        return logits[:, 0], cache

    def cache_slot_axes(self):
        """Batch-axis index per cache leaf (for slot-wise admission)."""
        if self.group == 1:
            return {"k": 1, "v": 1}
        return {"k_local": 2, "v_local": 2, "k_global": 1, "v_global": 1}

    def cache_max_seq(self, cache) -> int:
        key = "k" if self.group == 1 else "k_global"
        return cache[key].shape[2]

    def prefill_into_slot(self, params: Params, cache, tokens: jnp.ndarray,
                          slot, patch_embeds=None):
        """Prefill one prompt (1, P) and install its cache into ``slot`` of
        an existing slot-pool cache (continuous-batching admission).
        Returns (last-position logits (1, V), updated pool cache)."""
        logits, sub = self.prefill(params, tokens,
                                   patch_embeds=patch_embeds,
                                   max_seq=self.cache_max_seq(cache),
                                   remat=False)
        return logits, cm.write_cache_slot(cache, sub, slot,
                                           self.cache_slot_axes())

    def paged_cache_keys(self):
        """Cache leaves holding unbounded (max_seq) KV, eligible for the
        block-table page pool; local ring buffers stay dense (bounded W)."""
        return ["k", "v"] if self.group == 1 else ["k_global", "v_global"]

    def decode_step(self, params: Params, cache, tokens: jnp.ndarray,
                    pos: jnp.ndarray, block_tables=None):
        """One decode step. tokens: (B,) int32; pos: (B,) absolute position.

        With ``block_tables`` (B, nb), the leaves named by
        :meth:`paged_cache_keys` are page pools (P, page, KV, D) shared by
        all slots; reads go through the paged-attention path and writes
        scatter one token into the slot's current page.
        """
        cfg = self.cfg
        B = tokens.shape[0]
        x = cm.embed_tokens(params["embed"], tokens[:, None],
                            self.compute_dtype,
                            pos_offset=0) if cfg.positional != "learned" else \
            (jnp.take(params["embed"]["wte"], tokens[:, None], axis=0)
             + jnp.take(params["embed"]["wpe"], pos[:, None], axis=0)
             ).astype(self.compute_dtype)
        glayers = jax.tree.map(
            lambda a: a.reshape((self.n_groups, self.group) + a.shape[1:]),
            params["layers"])
        arangeB = jnp.arange(B)

        def one_attn(lp, x, kc, vc, window, ring: bool, ks=None, vs=None):
            paged = block_tables is not None and not ring
            h = cm.apply_norm(lp["norm_attn"], x, cfg.norm)
            q = jnp.einsum("bsd,dhk->bshk", h, cm.cast(lp["attn"]["wq"],
                                                       h.dtype))
            k = jnp.einsum("bsd,dhk->bshk", h, cm.cast(lp["attn"]["wk"],
                                                       h.dtype))
            v = jnp.einsum("bsd,dhk->bshk", h, cm.cast(lp["attn"]["wv"],
                                                       h.dtype))
            if cfg.positional == "rope":
                q = cm.apply_rope(q, pos[:, None], cfg.rope_theta)
                k = cm.apply_rope(k, pos[:, None], cfg.rope_theta)
            if paged:
                if ks is not None:
                    kc, ks = cm.paged_cache_write_quant(kc, ks, k[:, 0],
                                                        block_tables, pos)
                    vc, vs = cm.paged_cache_write_quant(vc, vs, v[:, 0],
                                                        block_tables, pos)
                else:
                    kc = cm.paged_cache_write(kc, k[:, 0], block_tables,
                                              pos)
                    vc = cm.paged_cache_write(vc, v[:, 0], block_tables,
                                              pos)
                o = cm.paged_decode_attention(q, kc, vc, block_tables,
                                              pos=pos, window=window,
                                              k_scales=ks, v_scales=vs)
            else:
                slot = pos % kc.shape[1] if ring else pos
                kc = kc.at[arangeB, slot].set(k[:, 0])
                vc = vc.at[arangeB, slot].set(v[:, 0])
                if ring:
                    W = kc.shape[1]
                    s = jnp.arange(W)[None, :]
                    abs_pos = pos[:, None] - ((pos[:, None] - s) % W)
                    o = self._ring_attention(q, kc, vc, abs_pos, pos)
                else:
                    o = cm.decode_attention(q, kc, vc, pos=pos,
                                            window=window)
            o = jnp.einsum("bshk,hkd->bsd", o, cm.cast(lp["attn"]["wo"],
                                                       h.dtype))
            x = x + o
            h = cm.apply_norm(lp["norm_mlp"], x, cfg.norm)
            if cfg.is_moe:
                h, _ = cm.apply_moe(
                    lp["moe"], h, n_experts=cfg.moe.n_experts,
                    top_k=cfg.moe.top_k,
                    capacity_factor=cfg.moe.capacity_factor,
                    activation=cfg.activation,
                    shared_expert=cfg.moe.shared_expert, drop=False)
            else:
                h = cm.apply_mlp(lp["mlp"], h, cfg.activation)
            return x + h, kc, vc, ks, vs

        def group_body(x, scanned):
            gp, gcache = scanned
            new_cache = dict(gcache)
            if self.group == 1:
                lp = jax.tree.map(lambda a: a[0], gp)
                x, kc, vc, ks, vs = one_attn(
                    lp, x, gcache["k"], gcache["v"], 0, ring=False,
                    ks=gcache.get("k_scale"), vs=gcache.get("v_scale"))
                new_cache["k"], new_cache["v"] = kc, vc
                if ks is not None:
                    new_cache["k_scale"], new_cache["v_scale"] = ks, vs
            else:
                kls, vls = [], []
                for i in range(self.group):
                    lp = jax.tree.map(lambda a, i=i: a[i], gp)
                    window = self._layer_window(i)
                    if window:
                        x, kc, vc, _, _ = one_attn(
                            lp, x, gcache["k_local"][i],
                            gcache["v_local"][i], window, ring=True)
                        kls.append(kc)
                        vls.append(vc)
                    else:
                        x, kc, vc, ks, vs = one_attn(
                            lp, x, gcache["k_global"], gcache["v_global"],
                            0, ring=False,
                            ks=gcache.get("k_global_scale"),
                            vs=gcache.get("v_global_scale"))
                        new_cache["k_global"] = kc
                        new_cache["v_global"] = vc
                        if ks is not None:
                            new_cache["k_global_scale"] = ks
                            new_cache["v_global_scale"] = vs
                new_cache["k_local"] = jnp.stack(kls)
                new_cache["v_local"] = jnp.stack(vls)
            return x, new_cache

        x, new_cache = lax.scan(group_body, x, (glayers, cache))
        logits = self.logits(params, x)
        return logits[:, 0], new_cache

    def _ring_attention(self, q, kc, vc, abs_pos, pos):
        """Attention over a ring-buffer cache with per-slot abs positions."""
        B, _, H, D = q.shape
        KV = kc.shape[2]
        G = H // KV
        qr = q.reshape(B, KV, G, D) * (D ** -0.5)
        s = jnp.einsum("bkgd,bskd->bkgs", qr, kc,
                       preferred_element_type=jnp.float32)
        valid = (abs_pos >= 0) & (abs_pos <= pos[:, None])
        s = jnp.where(valid[:, None, None, :], s, cm.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
        return o.reshape(B, 1, H, D).astype(q.dtype)

    # -- specs -------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every entry-point input."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32

        def sds(shp, dt=i32):
            return jax.ShapeDtypeStruct(tuple(shp), dt)

        if shape.kind == "train":
            specs = {"tokens": sds((B, S)), "targets": sds((B, S))}
            if cfg.family == "vlm":
                P = cfg.vision_prefix_len
                specs["tokens"] = sds((B, S - P))
                specs["targets"] = sds((B, S - P))
                specs["patch_embeds"] = sds((B, P, cfg.d_model),
                                            self.compute_dtype)
            return specs
        if shape.kind == "prefill":
            specs = {"tokens": sds((B, S))}
            if cfg.family == "vlm":
                P = cfg.vision_prefix_len
                specs["tokens"] = sds((B, S - P))
                specs["patch_embeds"] = sds((B, P, cfg.d_model),
                                            self.compute_dtype)
            return specs
        # decode: one new token against a cache of size S
        return {"tokens": sds((B,)), "pos": sds((B,)),
                "cache": self._cache_struct(B, S)}
