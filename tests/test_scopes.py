"""The named scopes of the training path and the names of the Pallas
kernels: what the benchmark reads each device op's layer from
(``bench/scopes.py``)."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, smoke_config
from repro.kernels.flash_attention.backward import flash_attention_bwd
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.paged import paged_flash_decode
from repro.kernels.rmsnorm.kernel import rmsnorm_rows
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.models import build_model
from repro.obs import SCOPES, scope
from repro.train import OptimizerConfig, init_train_state, make_train_step


def _op_names(hlo: str):
    return set(re.findall(r'op_name="([^"]+)"', hlo))


def _scoped(names, name, backward):
    """Whether some op_name has ``name`` as a path component, under a
    transposition (the backward pass) or not."""
    part = re.compile(r"(^|[/;(])" + name + r"([/;)]|$)")
    return any(part.search(n) and ("transpose(" in n) == backward
               for n in names)


@pytest.fixture(scope="module")
def train_step_names():
    """op_names of the optimized HLO of a small GPT-3-shaped train step
    (LayerNorm, GELU, learned positions, untied head) with remat and two
    microbatches."""
    cfg = smoke_config(REGISTRY["gpt3-xl"])
    model = build_model(cfg)
    step = make_train_step(model, OptimizerConfig(name="adamw", lr=1e-3),
                           accum_steps=2, remat=True)
    state = jax.eval_shape(lambda k: init_train_state(model, k),
                           jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((4, 32), jnp.int32)
             for k in ("tokens", "targets")}
    return _op_names(jax.jit(step).lower(state, batch).compile().as_text())


@pytest.mark.parametrize("name", ["attn", "mlp", "norm"])
def test_layer_scopes_forward_and_backward(train_step_names, name):
    assert _scoped(train_step_names, name, backward=False)
    assert _scoped(train_step_names, name, backward=True)


@pytest.mark.parametrize("name", ["embed", "head", "grad_accum", "opt"])
def test_step_scopes_in_compiled_step(train_step_names, name):
    assert any(re.search(r"(^|[/;(])" + name + r"([/;)]|$)", n)
               for n in train_step_names)


def test_scope_refuses_unknown_name():
    with pytest.raises(ValueError, match="unknown scope"):
        scope("attention")
    assert scope("mlp") is not None
    assert len(set(SCOPES)) == len(SCOPES) == 7


def test_bench_reads_the_program_vocabulary():
    """The benchmark keeps its own copy of the vocabulary, so that it does
    not import the program; a scope missing from the copy would charge its
    ops to the enclosing scope."""
    from bench import scopes
    assert scopes.SCOPES == SCOPES


def _pallas_names(fn, *args):
    """Names of the pallas_calls in the jaxpr of ``fn``, nested jaxprs
    included."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_every_pallas_call_is_named():
    bh = _sds((2, 128, 64))
    lse = _sds((2, 128, 1))
    assert _pallas_names(lambda q, k, v: flash_attention_fwd(
        q, k, v, block_q=64, block_k=64, interpret=True),
        bh, bh, bh) == ["flash_fwd"]
    assert _pallas_names(lambda q, k, v, o, do, l: flash_attention_bwd(
        q, k, v, o, do, l, block_q=64, block_k=64, interpret=True),
        bh, bh, bh, bh, bh, lse) == ["flash_bwd_dq", "flash_bwd_dkv"]
    assert _pallas_names(lambda q, k, v, t, p: paged_flash_decode(
        q, k, v, t, p, interpret=True),
        _sds((2, 1, 4, 64)), _sds((8, 16, 2, 64)), _sds((8, 16, 2, 64)),
        _sds((2, 4), jnp.int32), _sds((2,), jnp.int32)) == ["paged_decode"]
    assert _pallas_names(lambda x, w: rmsnorm_rows(x, w, interpret=True),
                         _sds((256, 128)), _sds((128,))) == ["rmsnorm"]
    assert _pallas_names(lambda x, a, b, c: ssd_scan(
        x, a, b, c, chunk=16, interpret=True),
        _sds((1, 32, 2, 16)), _sds((1, 32, 2)), _sds((1, 32, 2, 16)),
        _sds((1, 32, 2, 16))) == ["ssd_scan"]
