"""Moonlight-16B-A3B's parts on the CPU: the flash kernels with values
narrower than queries and keys (latent attention), the dropless routed
expert layer, and the share of the experts one chip holds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, smoke_config
from repro.kernels.flash_attention.backward import flash_attention_bwd
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention_train
from repro.kernels.flash_attention.ref import attention_ref
from repro.models import build_model
from repro.models import common as cm

RNG = np.random.default_rng(11)


def _normal(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


@pytest.mark.parametrize("subtile", [16, 8])
def test_flash_kernels_with_narrower_values(subtile):
    """Causal, 4 x 4 tiles of 16, q/k of 24 and v of 16: the forward's
    output and log-sum-exp path and the dq and dk/dv kernels agree with
    the float32 oracle's values and gradients, diagonal tiles computed
    whole (sub-tile 16) or as strips (8)."""
    BH, S, D, Dv = 3, 64, 24, 16
    q, k, v = _normal(BH, S, D), _normal(BH, S, D), _normal(BH, S, Dv)
    w = _normal(BH, S, Dv)
    kw = dict(causal=True, block_q=16, block_k=16, interpret=True,
              _subtile=subtile)
    o, lse = flash_attention_fwd(q, k, v, return_lse=True, **kw)
    ref = lambda q, k, v: attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    grads = flash_attention_bwd(q, k, v, o, w, lse, **kw)
    want = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), grads, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_flash_train_with_narrower_values():
    """The differentiable wrapper in the model's layout (B, S, H, D), values
    of 16 under heads of 24."""
    B, S, H, D, Dv = 2, 48, 2, 24, 16
    q, k, v = _normal(B, S, H, D), _normal(B, S, H, D), _normal(B, S, H, Dv)
    w = _normal(B, S, H, Dv)

    def ref(q, k, v):
        t = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, -1)
        o = attention_ref(t(q), t(k), t(v), causal=True)
        return o.reshape(B, H, S, Dv).transpose(0, 2, 1, 3)

    kern = lambda q, k, v: flash_attention_train(q, k, v, True, 0, 16, 16,
                                                 True)
    np.testing.assert_allclose(np.asarray(kern(q, k, v)),
                               np.asarray(ref(q, k, v)), rtol=2e-5,
                               atol=2e-5)
    gk = jax.grad(lambda *a: jnp.sum(kern(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def _layer(E=16, held=16, K=6, d=32, f=16, seed=0):
    moe = dataclasses.replace(REGISTRY["moonlight-16b-a3b"].moe,
                              n_experts=E, experts_held=held, top_k=K,
                              d_expert=f)
    b = cm.ParamBuilder("init", jax.random.PRNGKey(seed))
    return moe, cm.init_routed_moe(b, d, 4 * f, moe)


def _dense_moe(p, x, moe, first=0):
    """Every token through every held expert, weighted by its gate: the
    plain form of the routed part of the layer."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    s = jax.nn.sigmoid(xt @ p["router"])
    _, idx = jax.lax.top_k(s + p["select_bias"], moe.top_k)
    w = jnp.take_along_axis(s, idx, 1)
    w = w / w.sum(-1, keepdims=True) * moe.routed_scale
    gate = jnp.zeros_like(s).at[jnp.arange(len(xt))[:, None], idx].set(w)
    y = 0.0
    for e in range(p["w_gate"].shape[0]):
        h = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
        y = y + gate[:, first + e:first + e + 1] * (h @ p["w_down"][e])
    return y.reshape(B, S, d)


def _routed(p, x, moe, first=0):
    return cm.apply_routed_moe(
        p, x, n_experts=moe.n_experts, top_k=moe.top_k,
        routed_scale=moe.routed_scale, first=first)


def test_dropless_under_a_skewed_router():
    """A router that sends every token to experts 0..5: expert 0 gets all
    64 tokens (ten times an even share), and every one is computed."""
    moe, p = _layer()
    p = {**p, "select_bias": jnp.where(jnp.arange(16) < 6, 10.0, 0.0)}
    x = _normal(2, 32, 32)
    y, aux = _routed(p, x, moe)
    no_shared = {k: v for k, v in p.items() if k != "shared"}
    y0, _ = _routed(no_shared, x, moe)
    np.testing.assert_allclose(np.asarray(y0),
                               np.asarray(_dense_moe(p, x, moe)),
                               rtol=1e-5, atol=1e-5)
    load = np.asarray(aux["expert_load"])
    np.testing.assert_array_equal(load[:6], 64)
    assert load[6:].sum() == 0
    assert float(aux["routed_rows_here"]) == 64 * 6
    assert float(aux["max_expert_load"]) == 64
    np.testing.assert_allclose(
        np.asarray(y - y0),
        np.asarray(cm.apply_mlp(p["shared"], x, "swiglu")), rtol=1e-5,
        atol=1e-5)


def test_held_shares_add_up_to_the_whole_layer():
    """Eight chips, each holding 2 of 16 experts: the parts their shares
    give, with the shared expert counted once, add up to the layer that
    holds all 16; each share computes only its own experts' rows."""
    moe_all, p = _layer(E=16, held=16)
    moe_one = dataclasses.replace(moe_all, experts_held=2)
    x = _normal(2, 24, 32)
    whole, aux = _routed(p, x, moe_all)
    routed = {k: v for k, v in p.items() if k != "shared"}
    parts, rows = 0.0, 0.0
    for chip in range(8):
        sl = slice(2 * chip, 2 * chip + 2)
        share = {**routed, **{k: p[k][sl] for k in
                              ("w_gate", "w_up", "w_down")}}
        y, a = _routed(share, x, moe_one, first=2 * chip)
        parts = parts + y
        rows += float(a["routed_rows_here"])
    shared = cm.apply_mlp(p["shared"], x, "swiglu")
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert rows == float(aux["routed_rows_here"]) == 2 * 24 * moe_all.top_k


@pytest.mark.parametrize("tokens, held, n_experts, cap", [
    (8192, 8, 64, 12288),     # train-moonlight-ep8: a quarter of 49,152
    (1024, 2, 16, 1536),
    (1024, 1, 64, 512),       # at least one row tile
    (8192, 32, 64, None),     # half the experts: the cap would be T*K
    (8192, 64, 64, None),
])
def test_routed_cap(tokens, held, n_experts, cap):
    """Twice the rows the held experts expect, in whole row tiles of the
    grouped matmul, and no cap where that is not under T*K."""
    assert cm.routed_cap(tokens, 6, held, n_experts) == cap


def _routed_grads(p, x, moe, first=0, layer=None):
    """The routed part's value and its gradients to x, the three expert
    weights and the router, under a fixed projection of the output;
    ``layer`` (p, x, moe, first) -> y in place of the routed layer."""
    routed = {k: v for k, v in p.items() if k != "shared"}
    proj = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def f(x, w):
        if layer is not None:
            y, aux = layer({**routed, **w}, x, moe, first), {}
        else:
            y, aux = _routed({**routed, **w}, x, moe, first)
        return jnp.sum(y * proj), (y, aux)

    keys = ("w_gate", "w_up", "w_down", "router")
    (_, (y, aux)), grads = jax.value_and_grad(f, argnums=(0, 1),
                                              has_aux=True)(
        x, {k: p[k] for k in keys})
    return y, aux, {"x": grads[0], **grads[1]}


def _assert_same(got, want):
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


@pytest.mark.parametrize("first", [0, 6])
def test_capped_buffer_matches_the_full_one(monkeypatch, first):
    """2 of 16 experts held, 1024 tokens of top-6 under an even router: the
    held copies (about 768) fit the cap of 1536 rows, and the capped
    buffer gives the full buffer's values and gradients, and the plain
    form's."""
    moe, p = _layer(E=16, held=2)
    p = {**p, **{k: p[k][:2] for k in ("w_gate", "w_up", "w_down")}}
    moe = dataclasses.replace(moe, experts_held=2)
    x = _normal(2, 512, 32)
    y, aux, grads = _routed_grads(p, x, moe, first)
    assert float(aux["routed_overflow"]) == 0.0
    assert 0 < float(aux["routed_rows_here"]) <= 1536
    monkeypatch.setattr(cm, "routed_cap", lambda *a: None)
    y_all, aux_all, grads_all = _routed_grads(p, x, moe, first)
    assert float(aux_all["routed_overflow"]) == 1.0
    _assert_same({"y": y, **grads}, {"y": y_all, **grads_all})
    y_ref, _, grads_ref = _routed_grads(p, x, moe, first, _dense_moe)
    _assert_same({"y": y, **grads}, {"y": y_ref, **grads_ref})


def test_skewed_router_takes_the_exact_fallback(monkeypatch):
    """Every token to experts 0..5 with experts 0 and 1 held here: 2048
    copies overflow the cap of 1536 rows, the buffer of all copies runs
    instead, and every copy is computed, as the plain form says; the
    values and gradients are those of the uncapped layer and of the plain
    form."""
    moe, p = _layer(E=16, held=2)
    p = {**p, **{k: p[k][:2] for k in ("w_gate", "w_up", "w_down")},
         "select_bias": jnp.where(jnp.arange(16) < 6, 10.0, 0.0)}
    moe = dataclasses.replace(moe, experts_held=2)
    x = _normal(2, 512, 32)
    y, aux, grads = _routed_grads(p, x, moe)
    assert float(aux["routed_rows_here"]) == 2048
    assert float(aux["routed_overflow"]) == 1.0
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_dense_moe(p, x, moe)),
                               rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(cm, "routed_cap", lambda *a: None)
    y_all, _, grads_all = _routed_grads(p, x, moe)
    _assert_same({"y": y, **grads}, {"y": y_all, **grads_all})
    _, _, grads_ref = _routed_grads(p, x, moe, layer=_dense_moe)
    _assert_same(grads, grads_ref)


@pytest.mark.parametrize("held, conds", [(16, 0), (2, 2)])
def test_a_chip_holding_every_expert_runs_no_cond(held, conds):
    """The cap and its fallback exist only where a chip holds fewer than
    half the experts: one ``cond`` forward and one backward, none where
    the buffer of all copies is the only one."""
    moe, p = _layer(E=16, held=16)
    p = {**p, **{k: p[k][:held] for k in ("w_gate", "w_up", "w_down")}}
    moe = dataclasses.replace(moe, experts_held=held)
    x = _normal(2, 512, 32)
    loss = lambda x: jnp.sum(_routed(p, x, moe)[0])
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(x))
    assert jaxpr.count(" cond[") == conds


def test_selection_bias_rule():
    """After a step each expert's bias moves by 1e-3 toward the mean load;
    the optimizer leaves it alone (no gradient, no decay)."""
    cfg = smoke_config(REGISTRY["moonlight-16b-a3b"])
    model = build_model(cfg, block_k=16)
    params = model.init(jax.random.PRNGKey(0))
    load = jnp.asarray([[1.0, 5.0, 3.0, 3.0]])
    new = model.update_frozen(params, {"expert_load": load})
    np.testing.assert_allclose(
        np.asarray(new["layers"]["moe"]["select_bias"]),
        [[1e-3, -1e-3, 0.0, 0.0]])
    mask = model.frozen_mask()
    assert mask["layers"]["moe"]["select_bias"] is True
    assert not any(jax.tree.leaves(
        {k: v for k, v in mask.items() if k != "layers"}))


def test_grouped_matmul_kernel_matches_ragged_dot(monkeypatch):
    """The TPU's grouped matmul (the megablox kernel, in interpret mode
    here) against ``lax.ragged_dot``: rows of 3 groups, the rows after
    them zero, values and both gradients."""
    x, w = _normal(256, 64), _normal(3, 64, 32)
    sizes = jnp.asarray([40, 0, 90], jnp.int32)
    ref = lambda x, w: cm.grouped_matmul(x, w, sizes)
    want = ref(x, w)
    monkeypatch.setattr(cm, "_attention_kernels",
                        lambda: {"interpret": True})
    got = cm.grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert not np.asarray(got[130:]).any()
    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(f(x, w)))
    gk = jax.grad(loss(lambda x, w: cm.grouped_matmul(x, w, sizes)),
                  argnums=(0, 1))(x, w)
    monkeypatch.undo()
    gr = jax.grad(loss(ref), argnums=(0, 1))(x, w)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_layer_matches_the_published_deepseek_v3_layer(monkeypatch):
    """One MoE layer of a small Moonlight against the installed
    ``transformers`` ``DeepseekV3DecoderLayer`` (eager attention, all 8
    experts held, a nonzero selection bias), float32: latent attention
    with interleaved RoPE, the sigmoid router, the normalised and scaled
    top-6 and the shared experts agree."""
    monkeypatch.setenv("USE_TF", "0")
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as hf
    from repro.configs import MLAConfig, MoEConfig, ModelConfig

    d, H, E, f, r = 64, 4, 8, 32, 32
    hc = DeepseekV3Config(
        hidden_size=d, num_attention_heads=H, num_key_value_heads=H,
        q_lora_rank=None, kv_lora_rank=r, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=E,
        num_experts_per_tok=6, n_group=1, topk_group=1, n_shared_experts=2,
        moe_intermediate_size=f, intermediate_size=96,
        first_k_dense_replace=1, num_hidden_layers=2, rope_theta=50000.0,
        routed_scaling_factor=2.446, norm_topk_prob=True, rms_norm_eps=1e-5,
        vocab_size=256, rope_interleave=True, rope_scaling=None)
    hc._attn_implementation = "eager"
    cfg = ModelConfig(
        name="small", family="moe", n_layers=2, n_dense_layers=1,
        d_model=d, n_heads=H, n_kv_heads=H, d_ff=96, vocab_size=256,
        head_dim=24, rope_theta=50000.0, compute_dtype="float32",
        mla=MLAConfig(r, 16, 8, 16),
        moe=MoEConfig(n_experts=E, top_k=6, dropless=True, d_expert=f,
                      n_shared_experts=2, routed_scale=2.446,
                      bias_rate=1e-3))
    model = build_model(cfg, block_k=16)
    lp = jax.tree.map(lambda a: np.asarray(a[0]),
                      model.init(jax.random.PRNGKey(0))["layers"])
    lp["moe"]["select_bias"] = RNG.normal(size=E).astype(np.float32) * 0.05
    a, m = lp["attn"], lp["moe"]
    sd = {"input_layernorm.weight": lp["norm_attn"]["scale"],
          "post_attention_layernorm.weight": lp["norm_mlp"]["scale"],
          "self_attn.q_proj.weight": a["wq"].reshape(d, -1).T,
          "self_attn.kv_a_proj_with_mqa.weight": a["w_kva"].T,
          "self_attn.kv_a_layernorm.weight": a["kv_norm"]["scale"],
          "self_attn.kv_b_proj.weight": a["w_kvb"].reshape(r, -1).T,
          "self_attn.o_proj.weight": a["wo"].reshape(-1, d).T,
          "mlp.gate.weight": m["router"].T,
          "mlp.gate.e_score_correction_bias": m["select_bias"]}
    for w in ("gate", "up", "down"):
        sd[f"mlp.shared_experts.{w}_proj.weight"] = m["shared"][f"w_{w}"].T
        for e in range(E):
            sd[f"mlp.experts.{e}.{w}_proj.weight"] = m[f"w_{w}"][e].T
    layer = hf.DeepseekV3DecoderLayer(hc, layer_idx=1).eval()
    layer.load_state_dict({k: torch.tensor(np.ascontiguousarray(v))
                           for k, v in sd.items()}, strict=True)
    B, S = 2, 40
    x = RNG.normal(size=(B, S, d)).astype(np.float32)
    pos = torch.arange(S)[None].expand(B, S)
    cos_sin = hf.DeepseekV3RotaryEmbedding(hc)(torch.tensor(x), pos)
    mask = torch.full((S, S), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = layer(torch.tensor(x), attention_mask=mask.expand(B, 1, S, S),
                     position_ids=pos, position_embeddings=cos_sin)
    want = (want[0] if isinstance(want, tuple) else want).numpy()
    with jax.default_matmul_precision("highest"):
        got, _ = model._layer_fwd(jax.tree.map(jnp.asarray, lp),
                                  jnp.asarray(x), 0, 0, {})
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
