"""shard_map MoE all-to-all exchange vs the dense reference (subprocess
with 4 host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_moe_all_to_all_matches_dense_reference():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.collectives import moe_all_to_all_sharded

        E, K, T, d, ff = 8, 2, 64, 16, 32
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        xt = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
        logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        top_w, top_e = jax.lax.top_k(probs, K)
        top_w = top_w / top_w.sum(-1, keepdims=True)
        w1 = jnp.asarray(rng.normal(size=(E, d, ff)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(E, ff, d)) * 0.1, jnp.float32)

        # dense reference: every expert on every token, combined by gates
        h = jnp.einsum("td,edf->etf", xt, w1)
        y_all = jnp.einsum("etf,efd->etd", jax.nn.relu(h), w2)
        gates = jnp.zeros((T, E)).at[
            jnp.arange(T)[:, None], top_e].set(top_w)
        ref = jnp.einsum("te,etd->td", gates, y_all)

        def act(local_eid, x, weights):
            w1_l, w2_l = weights          # (E/4, d, ff), (E/4, ff, d)
            h = jnp.einsum("td,tdf->tf", x, w1_l[local_eid])
            return jnp.einsum("tf,tfd->td", jax.nn.relu(h),
                              w2_l[local_eid])

        with jax.set_mesh(mesh):
            out = moe_all_to_all_sharded(
                mesh, xt, top_e, top_w, (w1, w2), act, n_experts=E,
                capacity_factor=8.0)   # high capacity: no drops -> exact
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        print("OK", err)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_moe_all_to_all_wire_is_true_all_to_all():
    """The compiled exchange contains all-to-all ops and NO (T,d)-sized
    all-reduce — the §Perf C-3 fix."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.collectives import moe_all_to_all_sharded
        from repro.hw.hlo_parse import analyze_hlo

        E, K, T, d, ff = 8, 2, 4096, 64, 128
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))

        def f(xt, top_e, top_w, w1, w2):
            def act(local_eid, x, weights):
                w1_l, w2_l = weights
                h = jnp.einsum("td,tdf->tf", x, w1_l[local_eid])
                return jnp.einsum("tf,tfd->td", jax.nn.relu(h), w2_l[local_eid])
            return moe_all_to_all_sharded(mesh, xt, top_e, top_w,
                                          (w1, w2), act, n_experts=E)

        sds = jax.ShapeDtypeStruct
        with jax.set_mesh(mesh):
            comp = jax.jit(f).lower(
                sds((T, d), jnp.float32), sds((T, K), jnp.int32),
                sds((T, K), jnp.float32), sds((E, d, ff), jnp.float32),
                sds((E, ff, d), jnp.float32)).compile()
        an = analyze_hlo(comp.as_text())
        assert an.collective["all-to-all_count"] >= 3, an.collective
        # all-reduce traffic must be far below the token-tensor size
        assert an.collective["all-reduce_bytes"] < T * d, an.collective
        print("OK", an.collective["all-to-all_bytes"])
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
