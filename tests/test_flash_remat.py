"""The layer checkpoint keeps the training flash kernel's output and
log-sum-exp (``models/common.py:remat``), so the backward of a
checkpointed layer stack runs the forward kernel once, not twice, and the
gradients are the ones the plain checkpoint gives."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import repro.models.common as cm
from repro.kernels.flash_attention import flash_attention_train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def flash_fwd_calls(jaxpr) -> int:
    """Forward-kernel ``pallas_call`` equations in ``jaxpr`` and every
    jaxpr nested in its equations (scan, checkpoint, custom_vjp,
    shard_map bodies)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                eqn.params["name"] == "flash_fwd":
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += flash_fwd_calls(sub)
    return n


def stack_grad(checkpoint, dv: int):
    """``jax.grad`` of a 2-layer ``lax.scan`` of attention layers, each
    under ``checkpoint``, through the flash kernels in interpret mode:
    q/k of 16 and v of ``dv`` over 2 heads, S 32 in blocks of 16."""
    B, S, H, d, dqk = 1, 32, 2, 8, 16
    rng = np.random.default_rng(0)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.3,
                                   jnp.float32)
    ws = {"wq": w(2, d, H, dqk), "wk": w(2, d, H, dqk),
          "wv": w(2, d, H, dv), "wo": w(2, H, dv, d)}
    x = w(B, S, d)

    def layer(x, p):
        q, k, v = (jnp.einsum("bsd,dhk->bshk", x, p[n])
                   for n in ("wq", "wk", "wv"))
        o = cm._per_device(lambda q, k, v: flash_attention_train(
            q, k, v, True, 0, 16, 16, True), q, k, v)
        return x + jnp.einsum("bshk,hkd->bsd", o, p["wo"]), None

    def loss(ws):
        y, _ = lax.scan(checkpoint(layer), x, ws)
        return (y ** 2).sum()

    return jax.grad(loss), ws


def plain(fn):
    return jax.checkpoint(fn, prevent_cse=False)


@pytest.mark.parametrize("dv", [16, 8], ids=["dv_eq_dqk", "dv_lt_dqk"])
def test_remat_runs_the_flash_forward_once(dv):
    grad, ws = stack_grad(cm.remat, dv)
    plain_grad, _ = stack_grad(plain, dv)
    assert flash_fwd_calls(jax.make_jaxpr(grad)(ws).jaxpr) == 1
    assert flash_fwd_calls(jax.make_jaxpr(plain_grad)(ws).jaxpr) == 2
    got, want = grad(ws), plain_grad(ws)
    for name in ws:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), err_msg=name)


def test_remat_runs_the_flash_forward_once_under_a_mesh():
    """Under a mesh the kernel runs inside ``shard_map``: the names still
    reach the policy, and the gradients match the plain checkpoint's
    exactly and the run without a mesh to float32 rounding."""
    code = textwrap.dedent("""
        import jax, numpy as np
        import repro.models.common as cm
        from repro.launch.mesh import make_host_mesh
        from test_flash_remat import flash_fwd_calls, plain, stack_grad
        for dv in (16, 8):
            grad, ws = stack_grad(cm.remat, dv)
            plain_grad, _ = stack_grad(plain, dv)
            unsharded = grad(ws)
            with jax.set_mesh(make_host_mesh(2, 1)):
                jaxpr = jax.make_jaxpr(grad)(ws)
                assert "shard_map" in str(jaxpr)
                assert flash_fwd_calls(jaxpr.jaxpr) == 1
                assert flash_fwd_calls(
                    jax.make_jaxpr(plain_grad)(ws).jaxpr) == 2
                got, want = jax.jit(grad)(ws), jax.jit(plain_grad)(ws)
            for name in ws:
                np.testing.assert_array_equal(np.asarray(got[name]),
                                              np.asarray(want[name]))
                np.testing.assert_allclose(np.asarray(got[name]),
                                           np.asarray(unsharded[name]),
                                           rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), os.path.join(REPO, "tests")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
