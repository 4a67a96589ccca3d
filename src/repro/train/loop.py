"""Fault-tolerant training driver with executed kernel-level DVFS.

Integrates: jitted train step, data pipeline (resumable cursor),
checkpoint-every-N with atomic save, automatic restart from the latest
checkpoint on (injected or real) failure, straggler watchdog, and DVFS
execution per step.  This is the loop ``examples/train_gpt3xl_dvfs.py``
and the FT tests drive.

DVFS integration comes in two tiers:

* ``energy_meter`` — passive accounting: an
  :class:`~repro.runtime.energy.EnergyMeter` integrates the analytic
  time/energy of a fixed schedule each step (no actuation);
* ``executor`` — active execution: a
  :class:`~repro.dvfs.TrainGovernorExecutor` (usually built with
  :meth:`~repro.dvfs.DvfsSession.train_executor`) *actuates* the planned
  clocks around every step, replaying the governor's
  :class:`~repro.dvfs.DvfsPlan` ``fwd``/``bwd``/``opt`` segments through
  a ``FrequencyController`` backend and metering each phase against its
  auto-governor twin.

The executor composes with fault tolerance: its accounting state is
checkpointed alongside model state (``extra["dvfs_exec"]``) and restored
on restart, so a mid-run failure resumes the plan's energy books instead
of resetting them; steps re-run after a restart are re-metered, which
matches the energy the hardware actually spent.  The run report's
``"dvfs"`` key carries the executor's per-phase summary.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..ckpt import CheckpointManager
from ..data import DataPipeline
from ..dvfs.executor import TrainGovernorExecutor
from ..runtime.energy import EnergyMeter
from ..runtime.ft import FailureInjector, InjectedFailure, StragglerWatchdog
from .step import TrainState, init_train_state


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    log_every: int = 10
    max_restarts: int = 3


class Trainer:
    def __init__(self, model, train_step: Callable, pipeline: DataPipeline,
                 ckpt: CheckpointManager, cfg: TrainerConfig,
                 energy_meter: Optional[EnergyMeter] = None,
                 executor: Optional[TrainGovernorExecutor] = None,
                 failure_injector: Optional[FailureInjector] = None,
                 seed: int = 0):
        self.model = model
        # the step consumes its input state: donating it lets the update
        # reuse the parameter and optimizer buffers instead of holding a
        # second copy of both on the device
        self.train_step = jax.jit(train_step, donate_argnums=0)
        self.pipeline = pipeline
        self.ckpt = ckpt
        self.cfg = cfg
        self.meter = energy_meter
        self.executor = executor
        self.injector = failure_injector
        self.watchdog = StragglerWatchdog()
        self.seed = seed
        self.history: List[Dict] = []
        self.restarts = 0

    # ------------------------------------------------------------------
    def _fresh_state(self) -> TrainState:
        return init_train_state(self.model, jax.random.PRNGKey(self.seed))

    def _restore_or_init(self) -> (Any, int):
        step = self.ckpt.latest_step()
        if step is None:
            if self.executor is not None:
                # no checkpoint to resume: drop any books from an aborted
                # attempt so re-run steps are not double-counted
                self.executor.reset()
            return self._fresh_state(), 0
        template = jax.tree.map(np.asarray, self._fresh_state())
        state, index = self.ckpt.restore(template)
        extra = index.get("extra", {})
        if "pipeline" in extra:
            self.pipeline.load_state_dict(extra["pipeline"])
        if self.executor is not None:
            if "dvfs_exec" in extra:
                # resume the plan's energy books mid-run (FT drill)
                self.executor.load_state_dict(extra["dvfs_exec"])
            else:
                # checkpoint predates the executor: start its books at
                # the restored step rather than keeping stale records
                self.executor.reset()
        return state, int(index["step"])

    def _save(self, step: int, state: TrainState):
        extra = {"pipeline": self.pipeline.state_dict()}
        if self.executor is not None:
            extra["dvfs_exec"] = self.executor.state_dict()
        self.ckpt.save(step, state, extra=extra)

    # ------------------------------------------------------------------
    def run(self) -> Dict:
        """Run to total_steps, restarting from checkpoints on failure."""
        while True:
            try:
                return self._run_once()
            except InjectedFailure as e:
                self.restarts += 1
                if self.restarts > self.cfg.max_restarts:
                    raise RuntimeError(
                        f"exceeded max_restarts: {e}") from e
                # simulate scheduler restarting the job
                continue

    def _run_once(self) -> Dict:
        state, start = self._restore_or_init()
        for step in range(start, self.cfg.total_steps):
            if self.injector is not None:
                self.injector.check(step)
            batch = self.pipeline.next_batch()
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            t0 = time.perf_counter()
            state, metrics = self.train_step(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            self.watchdog.observe(step, dt)
            rec = {"step": step, "loss": loss, "wall_s": dt,
                   "restarts": self.restarts}
            if self.meter is not None:
                e = self.meter.on_step(step)
                rec.update({"sim_time_s": e.time_s,
                            "sim_energy_j": e.energy_j})
            if self.executor is not None:
                e = self.executor.on_step(step)
                rec.update({"dvfs_time_s": e.time_s,
                            "dvfs_energy_j": e.energy_j})
            self.history.append(rec)
            next_step = step + 1
            if next_step % self.cfg.ckpt_every == 0 \
                    or next_step == self.cfg.total_steps:
                self._save(next_step, state)
        out = {"final_step": self.cfg.total_steps,
               "final_loss": self.history[-1]["loss"] if self.history
               else None,
               "restarts": self.restarts,
               "straggler_events": len(self.watchdog.events)}
        if self.meter is not None:
            out["energy"] = self.meter.totals()
        if self.executor is not None:
            self.executor.finish()
            out["dvfs"] = self.executor.summary()
        return out
