"""The float32 GPT-3 reference is tied to the model it checks: at a small
size of gpt3-xl on the CPU it draws the program's weights from the same
seed, and its logits, loss and three AdamW steps agree with the
program's ``build_model`` and train step computed in float32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import traffic as T
from bench import train

H.import_program()
from repro.configs import get_config, smoke_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train import (OptimizerConfig, init_train_state,  # noqa: E402
                         make_train_step)

REF = H.load_module(H.BENCH / "reference" / "gpt3.py")
CFG = H.load_json(H.BENCH / "configs" / "gpt3-xl-6L.json")


@pytest.fixture(scope="module")
def small():
    """gpt3-xl's smoke configuration, kept multi-head (one K/V head per
    query head) as gpt3-xl is, computing in float32."""
    cfg = smoke_config(get_config("gpt3-xl"))
    cfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads,
                              compute_dtype="float32")
    m = {k: getattr(cfg, k) for k in CFG["model"]}
    return cfg, m, build_model(cfg)


def flat(params):
    return {"/".join(k.key for k in path): np.asarray(a) for path, a in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def test_same_weights_from_the_seed(small):
    cfg, m, model = small
    key = jax.random.PRNGKey(2**31 - 3)
    prog = flat(jax.jit(model.init)(key))
    ref = jax.jit(lambda k: REF.init(m, k))(key)
    assert sorted(prog) == sorted(ref)
    for k in prog:
        np.testing.assert_array_equal(prog[k], np.asarray(ref[k]), err_msg=k)


def test_logits_and_loss(small):
    cfg, m, model = small
    params = jax.jit(model.init)(jax.random.PRNGKey(7))
    ref_p = jax.jit(lambda k: REF.init(m, k))(jax.random.PRNGKey(7))
    tok = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 48),
                                            dtype=np.int32)
    tgt = np.roll(tok, -1, axis=1)
    x = model._embed_input(params, jnp.asarray(tok))
    x, _ = model.forward_hidden(params, x, remat=False)
    prog = np.asarray(model.logits(params, x))[..., :cfg.vocab_size]
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(REF.logits(m, ref_p, jnp.asarray(tok)))
        np.testing.assert_allclose(prog, ref, atol=2e-5, rtol=1e-5)
        loss, _ = model.loss(params, {"tokens": jnp.asarray(tok),
                                      "targets": jnp.asarray(tgt)})
        assert float(loss) == pytest.approx(
            float(REF.loss(m, ref_p, jnp.asarray(tok), jnp.asarray(tgt))),
            abs=1e-5)


def test_three_adamw_steps(small):
    cfg, m, model = small
    opt = dict(CFG["train"]["optimizer"])
    step = jax.jit(make_train_step(model, OptimizerConfig(**opt),
                                   accum_steps=2, remat=True))
    key = jax.random.PRNGKey(11)
    mix = dict(H.load_json(H.BENCH / "traffic" / "paper-batch40.json"),
               global_batch=4, seq_len=32)
    blocks = [T.train_block(mix, cfg.vocab_size, 3, i) for i in range(3)]
    with jax.default_matmul_precision("highest"):
        state = jax.jit(lambda k: init_train_state(model, k))(key)
        losses = []
        for i, blk in enumerate(blocks):
            state, met = step(state, {"tokens": blk[:, :-1],
                                      "targets": blk[:, 1:]})
            losses.append(float(met["loss"]))
            if i == 0:
                grads = train.leaf_norms(state.opt["m"], 1 / (1 - opt["b1"]))
        p0 = model.init(jax.random.split(key)[0])
        delta = train.leaf_norms(jax.tree.map(jnp.subtract, state.params,
                                              p0))
    prog = {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grads.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}
    ref = REF.train_readings(m, opt, key, blocks, jax.devices()[:1], rows=2)
    gaps = train.compare(prog, ref)
    assert gaps["loss_gap"] < 1e-5
    assert gaps["grad_norm_gap"] < 1e-4
    assert gaps["update_norm_gap"] < 1e-3
