"""Elastic scaling: checkpoint saved on one mesh restores (resharded) onto
a different mesh — the grow/shrink recovery path."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_save_on_8_restore_on_4(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import CheckpointManager, reshard_restore
        from repro.configs import REGISTRY, smoke_config
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.parallel.sharding import param_specs

        cfg = smoke_config(REGISTRY["llama3.2-1b"])
        model = build_model(cfg, block_k=16)
        params = model.init(jax.random.PRNGKey(0))

        # place on 4x2 mesh, save
        mesh_a = make_host_mesh(4, 2)
        specs = param_specs(model, mesh_a)
        sh = jax.tree.map(lambda s: NamedSharding(mesh_a, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
        placed = jax.device_put(params, sh)
        mgr = CheckpointManager(r"{tmp_path}")
        mgr.save(1, placed)

        # restore resharded onto a 2x2 mesh (elastic shrink)
        mesh_b = make_host_mesh(2, 2)
        specs_b = param_specs(model, mesh_b)
        restored, _ = reshard_restore(mgr, params, mesh_b, specs_b)
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.shape == {{"data": 2, "model": 2}}
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
