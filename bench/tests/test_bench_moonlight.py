"""The float32 Moonlight reference is tied to the model it checks: at a
small size of the ``moonlight-16b-a3b-ep8`` configuration on the CPU (the
same keys, every width shrunk, 4 of 8 experts held) it draws the program's
weights from the same seed, and its logits, loss and three AdamW steps
with the selection-bias rule agree with the program's ``build_model`` and
train step computed in float32."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import traffic as T
from bench import train

H.import_program()
from repro.configs import ModelConfig  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train import (OptimizerConfig, init_train_state,  # noqa: E402
                         make_train_step)

REF = H.load_module(H.BENCH / "reference" / "moonlight.py")
CFG = H.load_json(H.BENCH / "configs" / "moonlight-16b-a3b-ep8.json")


def small_model() -> dict:
    """The cell's model section at a small size: 1 dense and 2 MoE layers,
    4 of 8 experts held, top-2, q/k heads of 16 + 8, v heads of 16."""
    m = copy.deepcopy(CFG["model"])
    m.update(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
             vocab_size=256, head_dim=24, max_train_seq=64,
             compute_dtype="float32")
    m["mla"] = {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                "qk_rope_head_dim": 8, "v_head_dim": 16}
    m["moe"].update(n_experts=8, experts_held=4, top_k=2, d_expert=32)
    return m


@pytest.fixture(scope="module")
def small():
    m = small_model()
    return m, build_model(ModelConfig(**m), block_k=16)


def flat(params):
    return {"/".join(k.key for k in path): np.asarray(a) for path, a in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def test_same_weights_from_the_seed(small):
    m, model = small
    key = jax.random.PRNGKey(2**31 - 3)
    prog = flat(jax.jit(model.init)(key))
    ref = jax.jit(lambda k: REF.init(m, k))(key)
    assert sorted(prog) == sorted(ref)
    for k in prog:
        np.testing.assert_array_equal(prog[k], np.asarray(ref[k]), err_msg=k)


def test_logits_and_loss(small):
    m, model = small
    params = jax.jit(model.init)(jax.random.PRNGKey(7))
    ref_p = jax.jit(lambda k: REF.init(m, k))(jax.random.PRNGKey(7))
    bias = ref_p.pop("layers/moe/select_bias")
    tok = np.random.default_rng(0).integers(0, m["vocab_size"], (2, 48),
                                            dtype=np.int32)
    tgt = np.roll(tok, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        x = model._embed_input(params, jnp.asarray(tok))
        x, aux = model.forward_hidden(params, x, remat=False)
        prog = np.asarray(model.logits(params, x))[..., :m["vocab_size"]]
        ref, bal, load = REF.forward(m, ref_p, bias, jnp.asarray(tok))
        np.testing.assert_allclose(prog, np.asarray(ref), atol=5e-5,
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(aux["expert_load"]),
                                      np.asarray(load))
        assert float(aux["balance"]) == pytest.approx(float(bal), rel=1e-5)
        loss, _ = model.loss(params, {"tokens": jnp.asarray(tok),
                                      "targets": jnp.asarray(tgt)})
        ref_loss, _ = REF.loss(m, ref_p, bias, jnp.asarray(tok),
                               jnp.asarray(tgt))
        assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)


def test_three_adamw_steps_and_the_bias_rule(small):
    m, model = small
    opt = dict(CFG["train"]["optimizer"], lr=1e-3)
    step = jax.jit(make_train_step(model, OptimizerConfig(**opt),
                                   accum_steps=2, remat=True))
    key = jax.random.PRNGKey(11)
    mix = dict(H.load_json(H.BENCH / "traffic" / "seq8k-batch8.json"),
               global_batch=4, seq_len=32)
    blocks = [T.train_block(mix, m["vocab_size"], 3, i) for i in range(3)]
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
    # the bias moved by the sign rule alone: 3 steps of +-1e-3 or 0
    moved = np.asarray(state.params["layers"]["moe"]["select_bias"]) / 1e-3
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-4)
    assert np.abs(moved).max() <= 3 and np.abs(moved).sum() > 0
    prog = {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grads.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}
    assert prog["grad_norms"]["layers/moe/select_bias"] == 0.0
    ref = REF.train_readings(m, opt, key, blocks, jax.devices()[:1], rows=2)
    assert "layers/moe/select_bias" not in ref["grad_norms"]
    gaps = train.compare(prog, ref)
    assert gaps["loss_gap"] < 1e-5
    assert gaps["grad_norm_gap"] < 1e-4
    assert gaps["update_norm_gap"] < 1e-3


def test_control_is_caught(small):
    """The reference rounded to float8 reads apart from the float32 one by
    far more than the program's float32 readings do."""
    m, _ = small
    opt = dict(CFG["train"]["optimizer"], lr=1e-3)
    mix = dict(H.load_json(H.BENCH / "traffic" / "seq8k-batch8.json"),
               global_batch=2, seq_len=32)
    blocks = [T.train_block(mix, m["vocab_size"], 5, i) for i in range(3)]
    args = (m, opt, jax.random.PRNGKey(3), blocks, jax.devices()[:1])
    ref = REF.train_readings(*args, rows=1)
    low = REF.train_readings(*args, rows=1, quant="float8_e4m3fn")
    gaps = train.compare(low, ref)
    assert gaps["grad_norm_gap"] > 1e-2


def test_sound_run_of_the_cell_is_correct(monkeypatch):
    """A whole run of ``train-moonlight-ep8`` through ``bench/run.py`` at
    the small size, checked against the reference under the
    configuration's own limits.  It computes in float32: at a width of 64
    the cell's bfloat16 rounding alone reads a loss gap near the limit set
    at the published widths."""
    from bench import run
    cfg = copy.deepcopy(CFG)
    cfg["model"] = small_model()
    cfg["train"].update(accum_steps=2)

    def find(name):
        b = H.load_json(H.ROOT / "BENCHMARK.json")
        cell = {w["name"]: w for w in b["workloads"]}[name]
        t = H.load_json(H.BENCH / "traffic" / f"{cell['traffic']}.json")
        t.update(global_batch=4, seq_len=64)
        return b, cell, cfg, t

    monkeypatch.setattr(H, "find_cell", find)
    monkeypatch.setattr(H, "enable_compile_cache", lambda: "off")
    result, checks, out = run.run_cell("train-moonlight-ep8", 2**31 + 7,
                                       0.5, False,
                                       devices=jax.devices()[:1],
                                       config=cfg)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
