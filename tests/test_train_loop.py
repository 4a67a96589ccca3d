"""Fault-tolerant trainer: checkpoint-restart under injected failures,
straggler watchdog, energy metering integration, loss decreases."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import REGISTRY, smoke_config, get_shape
from repro.core import (Campaign, WastePolicy, build_workload, get_chip,
                        global_plan, schedule_from_plan)
from repro.ckpt import CheckpointManager
from repro.data import DataPipeline
from repro.models import build_model
from repro.runtime import (EnergyMeter, FailureInjector, StragglerWatchdog)
from repro.train import OptimizerConfig, make_train_step
from repro.train.loop import Trainer, TrainerConfig

pytestmark = pytest.mark.slow


def make_trainer(tmp_path, total_steps=12, fail_at=(), meter=None):
    cfg = smoke_config(REGISTRY["gpt3-xl"])
    model = build_model(cfg, block_k=16)
    opt = OptimizerConfig(lr=1e-2, warmup_steps=2, decay_steps=100)
    step = make_train_step(model, opt, accum_steps=2, remat=False)
    pipeline = DataPipeline(vocab_size=cfg.vocab_size, batch_per_host=4,
                            seq_len=32)
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    trainer = Trainer(model, step, pipeline, ckpt,
                      TrainerConfig(total_steps=total_steps, ckpt_every=4,
                                    max_restarts=4),
                      energy_meter=meter,
                      failure_injector=FailureInjector(fail_at))
    return trainer


def test_loss_decreases(tmp_path):
    trainer = make_trainer(tmp_path, total_steps=14)
    out = trainer.run()
    first = np.mean([h["loss"] for h in trainer.history[:3]])
    last = np.mean([h["loss"] for h in trainer.history[-3:]])
    assert last < first, f"loss did not decrease: {first} -> {last}"


def test_checkpoint_restart_on_failure(tmp_path):
    trainer = make_trainer(tmp_path, total_steps=12, fail_at=(6, 9))
    out = trainer.run()
    assert out["final_step"] == 12
    assert out["restarts"] == 2
    # steps 4..6 were re-run after the restart from ckpt_4
    steps = [h["step"] for h in trainer.history]
    assert steps.count(5) >= 2


def test_restart_resumes_bit_identically(tmp_path):
    """The step donates its input state; a run that fails and restarts
    from its checkpoint still ends on exactly the uninterrupted run's
    state and losses."""
    clean = make_trainer(tmp_path / "clean", total_steps=12)
    clean.run()
    failed = make_trainer(tmp_path / "failed", total_steps=12, fail_at=(10,))
    out = failed.run()
    assert out["restarts"] == 1
    losses = {h["step"]: h["loss"] for h in clean.history}
    for h in failed.history:
        assert h["loss"] == losses[h["step"]], h
    final = [np.load(tmp_path / run / "ckpt_00000012" / "proc_0.npz")
             for run in ("clean", "failed")]
    assert sorted(final[0].files) == sorted(final[1].files)
    for key in final[0].files:
        np.testing.assert_array_equal(final[0][key], final[1][key])


def test_trainer_donates_the_state(tmp_path):
    trainer = make_trainer(tmp_path)
    state = trainer._fresh_state()
    batch = {k: jax.numpy.asarray(v)
             for k, v in trainer.pipeline.next_batch().items()}
    new_state, _ = trainer.train_step(state, batch)
    assert all(x.is_deleted() for x in jax.tree.leaves(state))
    assert not any(x.is_deleted() for x in jax.tree.leaves(new_state))


def test_too_many_failures_raises(tmp_path):
    trainer = make_trainer(tmp_path, total_steps=10,
                           fail_at=(1, 2, 3, 4, 5, 6))
    trainer.cfg = TrainerConfig(total_steps=10, ckpt_every=100,
                                max_restarts=2)
    trainer.injector = FailureInjector((1, 1, 1))
    # injector fires once per step value; craft repeated failures:

    class AlwaysFail:
        def __init__(self):
            self.n = 0

        def check(self, step):
            from repro.runtime.ft import InjectedFailure
            if step == 1:
                raise InjectedFailure("boom")
    trainer.injector = AlwaysFail()
    with pytest.raises(RuntimeError):
        trainer.run()


def test_energy_meter_integration(tmp_path):
    chip = get_chip("tpu-v5e")
    cfg = smoke_config(REGISTRY["gpt3-xl"])
    kernels = build_workload(cfg, get_shape("paper_gpt3xl"),
                             batch_override=4)
    camp = Campaign(chip, seed=0, n_reps=2)
    table = camp.run(kernels)
    plan = global_plan(table, WastePolicy(0.0))
    sched = schedule_from_plan(plan)
    meter = EnergyMeter(chip, kernels, schedule=sched)
    baseline = EnergyMeter(chip, kernels, schedule=None)
    trainer = make_trainer(tmp_path, total_steps=6, meter=meter)
    out = trainer.run()
    assert out["energy"]["steps"] == 6
    assert out["energy"]["energy_j"] > 0
    # the DVFS schedule must not exceed baseline energy
    assert meter._iter_energy <= baseline._iter_energy * 1.001


def test_straggler_watchdog():
    wd = StragglerWatchdog(alpha=0.5, threshold=1.5, warmup=2)
    for i in range(8):
        wd.observe(i, 1.0)
    ev = wd.observe(8, 5.0)
    assert ev is not None and ev.ratio > 3
    assert len(wd.events) == 1
    # EWMA not polluted by the outlier
    assert wd.ewma == pytest.approx(1.0)
