"""Vocab padding + attention sharding-fallback behaviors."""
import subprocess
import sys
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.common as cm
from repro.models.common import ParamBuilder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_unembed_pads_and_masks_odd_vocab():
    V, d = 257, 16   # 257 -> padded to 512
    b = ParamBuilder(ParamBuilder.INIT, jax.random.PRNGKey(0))
    p = cm.init_embedding(b, V, d, tie=True)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, d)),
                    jnp.float32)
    logits = cm.unembed(p, x)
    assert logits.shape == (2, 3, 512)
    assert bool(jnp.all(logits[..., V:] <= -1e29))       # masked pads
    assert bool(jnp.all(jnp.argmax(logits, -1) < V))     # never sampled


def test_unembed_no_pad_when_divisible():
    V, d = 512, 16
    b = ParamBuilder(ParamBuilder.INIT, jax.random.PRNGKey(0))
    p = cm.init_embedding(b, V, d, tie=False)
    x = jnp.ones((1, 2, d), jnp.float32)
    assert cm.unembed(p, x).shape == (1, 2, V)


def test_unembed_gradient_flows_only_to_real_rows():
    V, d = 5, 4
    b = ParamBuilder(ParamBuilder.INIT, jax.random.PRNGKey(0))
    p = cm.init_embedding(b, V, d, tie=True)
    x = jnp.ones((1, 1, d), jnp.float32)
    tgt = jnp.asarray([[2]], jnp.int32)

    def loss(p):
        lg = cm.unembed(p, x)
        return cm.softmax_cross_entropy(lg, tgt)

    g = jax.grad(loss)(p)
    assert bool(jnp.all(jnp.isfinite(g["wte"])))
    assert float(jnp.abs(g["wte"]).sum()) > 0


@pytest.mark.slow
def test_attention_seq_fallback_when_heads_dont_divide():
    """On a mesh whose model axis does not divide the head count, the
    attention computation shards over the sequence instead of replicating
    (per-device dot FLOPs stay ~1/devices of global)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        import repro.models.common as cm
        from repro.hw.hlo_parse import analyze_hlo
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        B, S, H, D = 4, 64, 6, 8     # H=6 does not divide model=4

        def f(q, k, v):
            return cm.chunked_attention(q, k, v, causal=True, block_k=32)

        sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        with jax.set_mesh(mesh):
            comp = jax.jit(f).lower(sds(B, S, H, D), sds(B, S, H, D),
                                    sds(B, S, H, D)).compile()
        an = analyze_hlo(comp.as_text())
        global_flops = 4 * B * H * S * S * D  # qk + pv
        # replicated would be ~global; sharded ~global/8
        assert an.flops < 0.5 * global_flops, (an.flops, global_flops)
        print("OK", an.flops / global_flops)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_moe_no_drop_keeps_every_token():
    from repro.configs import REGISTRY, smoke_config
    cfg = smoke_config(REGISTRY["granite-moe-1b-a400m"])
    b = ParamBuilder(ParamBuilder.INIT, jax.random.PRNGKey(0))
    p = cm.init_moe(b, cfg.d_model, cfg.d_ff, cfg.moe.n_experts,
                    cfg.activation, cfg.moe.shared_expert)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 16, cfg.d_model)), jnp.float32)
    _, aux_drop = cm.apply_moe(
        p, x, n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
        capacity_factor=0.5, activation=cfg.activation,
        shared_expert=False, drop=True)
    _, aux_keep = cm.apply_moe(
        p, x, n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
        capacity_factor=0.5, activation=cfg.activation,
        shared_expert=False, drop=False)
    assert float(aux_drop["dropped_frac"]) > 0.0   # tight capacity drops
    assert float(aux_keep["dropped_frac"]) == 0.0  # serving never drops
