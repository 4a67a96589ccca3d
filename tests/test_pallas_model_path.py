"""The Pallas kernels as the model's attention path must match the jnp
path: flash attention in training (loss AND grads) and the page-read
kernel in paged decode (served tokens).  The backend picks the path; the
tests steer it onto the kernels in interpret mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.common as cm
from conftest import make_requests, smoke_model
from repro.configs import REGISTRY, smoke_config
from repro.models import build_model
from repro.serve import ServeEngine

pytestmark = pytest.mark.slow


def _interpret_kernels(monkeypatch):
    monkeypatch.setattr(cm, "_attention_kernels",
                        lambda: {"interpret": True})


def test_cpu_backend_takes_the_jnp_path():
    assert jax.default_backend() == "cpu"
    assert cm._attention_kernels() is None


@pytest.mark.parametrize("arch", ["llama3.2-1b", "llama4-scout-17b-a16e"])
def test_pallas_attention_path_matches_jnp(arch, monkeypatch):
    cfg = dataclasses.replace(smoke_config(REGISTRY[arch]),
                              compute_dtype="float32")
    model = build_model(cfg, block_k=16)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                               jnp.int32),
    }

    def loss(p):
        return model.loss(p, batch, remat=False)[0]

    l_ref, g_ref = jax.value_and_grad(loss)(params)
    _interpret_kernels(monkeypatch)
    l_pal, g_pal = jax.value_and_grad(loss)(params)

    assert abs(float(l_ref) - float(l_pal)) < 1e-4
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_decode_kernel_path_matches_jnp(kv_dtype, monkeypatch):
    model, params, cfg = smoke_model("llama3.2-1b")

    def serve():
        eng = ServeEngine(model, params, batch_slots=2, max_seq=64,
                          paged=True, page_size=16, kv_dtype=kv_dtype)
        return [r.generated for r in eng.generate(make_requests(cfg))]

    ref = serve()
    _interpret_kernels(monkeypatch)
    assert serve() == ref
