"""Whole runs of the harness on the CPU at a small size.

``bench/run.py`` refuses to run without a TPU.  Past the chip check, a run
whose timed path is broken underneath reads ``correct`` false, for each
fault a cell can have: a train step that returns its state unchanged,
half of the batch left out with the mean over the rest, the sum across
the model axis left out, targets taken from the wrong position.  The
control (the reference computed with float8 operands in the program's
place) fails the limits too.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import check, harness as H, run

H.import_program()
import repro.models.common as cm  # noqa: E402
import repro.train as train_mod  # noqa: E402

SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
             d_ff=128, vocab_size=257, max_train_seq=512)
# each cell by its configuration and traffic files
CELLS = {"train-1chip": ("gpt3-xl-6L", "paper-batch40")}


def small_cell(monkeypatch, name):
    """The cell's configuration and traffic cut to a CPU size; the
    limits stay as the configuration file sets them."""
    config, traffic = CELLS[name]
    cfg = H.load_json(H.BENCH / "configs" / f"{config}.json")
    cfg["model"].update(SMALL)
    cfg["train"].update(accum_steps=2, mesh=None)
    cfg["check"]["reference_rows"] = 2

    def find(n):
        b = H.load_json(H.ROOT / "BENCHMARK.json")
        c = {"name": n, "config": config, "traffic": traffic, "chips": 1}
        t = H.load_json(H.BENCH / "traffic" / f"{traffic}.json")
        t.update(global_batch=4, seq_len=64)
        return b, c, cfg, t

    monkeypatch.setattr(H, "find_cell", find)
    monkeypatch.setattr(H, "enable_compile_cache", lambda: "off")
    return cfg


def run_small(monkeypatch, name, seed=2**31 + 5):
    cfg = small_cell(monkeypatch, name)
    result, checks, _ = run.run_cell(name, seed, 0.5, False,
                                     devices=jax.devices()[:1], config=cfg)
    return result, checks


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(H.BENCH / "run.py"),
                        "--workload", "train-1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(monkeypatch, name):
    result, checks = run_small(monkeypatch, name)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(json.loads(json.dumps(result))) == \
        ["correct", "attempted", "failed", "device", "metrics"]


def unchanged_state(monkeypatch):
    real = train_mod.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    monkeypatch.setattr(train_mod, "make_train_step", make)


def half_batch(monkeypatch):
    real = train_mod.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: step(
            state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(train_mod, "make_train_step", make)


def no_reduce(monkeypatch):
    """One model shard's partial sum of the MLP's row-parallel product
    goes on, as when the reduction across chips is left out."""
    real = cm.apply_mlp

    def mlp(p, x, activation):
        w = p["w_down"]
        half = w.shape[0] // 2
        return real(dict(p, w_down=w.at[half:].set(0)), x, activation)
    monkeypatch.setattr(cm, "apply_mlp", mlp)


def shifted_targets(monkeypatch):
    """The step trains each position on the token two ahead, as when the
    targets are shifted once more where the batch is built."""
    real = train_mod.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def wrong(state, batch):
            y = batch["targets"]
            return step(state, dict(batch, targets=jax.numpy.concatenate(
                [y[:, 1:], y[:, :1]], axis=1)))
        return wrong
    monkeypatch.setattr(train_mod, "make_train_step", make)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, no_reduce,
                                   shifted_targets])
def test_train_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    result, checks = run_small(monkeypatch, "train-1chip")
    assert not result["correct"], checks


def test_unreadable_reading_fails():
    ok, checks = H.check_limits({"gap": float("inf"), "n": 0.0},
                                {"gap": 1.0, "n": 0})
    assert not ok
    assert checks == {"gap": {"value": None, "limit": 1.0},
                      "n": {"value": 0.0, "limit": 0}}


def test_train_control_fails(monkeypatch):
    cfg = small_cell(monkeypatch, "train-1chip")
    _, _, _, traffic = H.find_cell("train-1chip")
    ref = H.load_module(H.BENCH / "reference" / "gpt3.py")
    readings = check.train_lower(cfg, traffic, 21, jax.devices()[:1], ref,
                                 ["control"])
    ok, checks = H.check_limits(
        {k.split(".", 1)[1]: v for k, v in readings.items()},
        {k: v for k, v in cfg["check"]["limits"].items()
         if k != "compiles_in_window"})
    assert not ok, checks


SHARDED = """
import copy, json, jax
from bench import harness as H, run
H.enable_compile_cache = lambda: "off"
real = H.find_cell
_, _, cfg, _ = real("train-1chip")
cfg = copy.deepcopy(cfg)
cfg["model"].update(%r)
cfg["train"].update(accum_steps=2, mesh=[2, 2])
cfg["check"]["reference_rows"] = 4
def find(n):
    b, c, _, t = real(n)
    return b, c, cfg, dict(t, global_batch=8, seq_len=64)
H.find_cell = find
result, checks, _ = run.run_cell("train-1chip", 2**31 + 9, 0.5, False,
                                 devices=jax.devices(), config=cfg)
print(json.dumps([result["correct"], result["device"]["count"], checks]))
""" % SMALL


def test_sharded_train_step_on_four_devices():
    """The 2x2 (data, model) mesh path of the train runner, and the
    reference sharded over the same four devices, on virtual CPU
    devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARDED], capture_output=True,
                       text=True, env=env, timeout=600, cwd=H.ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    correct, count, checks = json.loads(p.stdout.splitlines()[-1])
    assert count == 4
    assert correct, checks
