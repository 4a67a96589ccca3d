"""Train-step builder: microbatched grad accumulation + remat + AdamW.

The returned ``train_step(state, batch)`` is the function the dry-run
lowers on the production mesh.  Gradient accumulation runs as a
``lax.scan`` over microbatches, which (a) bounds live activation memory —
the knob that makes the biggest assigned cells fit HBM — and (b) lets XLA
overlap the DP gradient all-reduce of microbatch *k* with the compute of
*k+1* on real hardware (collective/compute overlap).

For kernel-level DVFS the step is segmented into the three train phases of
:data:`~repro.core.workload.TRAIN_PHASES` — ``fwd`` (embedding, forward
layers, loss head), ``bwd`` (backward pass), ``opt`` (the AdamW update
built here) — matching the kernel ``phase`` tags the
:class:`~repro.core.workload.WorkloadBuilder` emits for the same step.
:func:`~repro.dvfs.phase_plan.plan_train_phases` plans one
:class:`~repro.dvfs.DvfsPlan` segment per phase and the
:class:`~repro.dvfs.TrainGovernorExecutor` replays them around each call
of this function; the step's optimized HLO
(``jax.jit(train_step).lower(...).compile().as_text()``) can be fed back
to the planner for analytic-vs-compiled calibration
(:func:`~repro.core.workload.calibrate_workload_against_hlo`).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope
from .optimizer import OptimizerConfig, adamw_update, init_opt_state
from .grad import accumulate, zeros_like_f32, compress_grads, \
    decompress_grads


@dataclass
class TrainState:
    params: Any
    opt: Dict
    rng: jax.Array

    def tree_flatten(self):
        return (self.params, self.opt, self.rng), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt, s.rng), None),
    lambda aux, c: TrainState(*c))


def init_train_state(model, rng) -> TrainState:
    prng, srng = jax.random.split(rng)
    params = model.init(prng)
    return TrainState(params=params, opt=init_opt_state(params), rng=srng)


def make_train_step(model, opt_cfg: OptimizerConfig,
                    accum_steps: int = 1,
                    remat: bool = True,
                    compress: bool = False) -> Callable:
    """Build a jit-able train step.

    ``batch`` leaves must have leading dim ``global_batch``; with
    ``accum_steps > 1`` they are reshaped to (accum, micro, ...) and scanned.
    """

    def loss_fn(params, mb, rng):
        loss, metrics = model.loss(params, mb, rng=rng, remat=remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    # state the optimizer leaves alone, moved by the model's own rule
    frozen = getattr(model, "frozen_mask", lambda: None)()

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        rng, step_rng = jax.random.split(state.rng)

        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(state.params, batch, step_rng)
            with scope("grad_accum"):
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            def split(x):
                return x.reshape((accum_steps, x.shape[0] // accum_steps)
                                 + x.shape[1:])
            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                acc, rng = carry
                rng, k = jax.random.split(rng)
                (loss, metrics), grads = grad_fn(state.params, mb, k)
                with scope("grad_accum"):
                    acc = accumulate(acc, grads, 1.0 / accum_steps)
                return (acc, rng), (loss, metrics)

            with scope("grad_accum"):
                acc0 = zeros_like_f32(state.params)
            (grads, _), (losses, metricses) = lax.scan(
                body, (acc0, step_rng), micro)
            loss = losses.mean()
            metrics = jax.tree.map(lambda m: m.mean(axis=0), metricses)

        if compress:
            crng = jax.random.fold_in(rng, 1)
            grads, _ = compress_grads(grads, crng)
            grads = decompress_grads(grads)

        with scope("opt"):
            params, opt, opt_metrics = adamw_update(
                state.params, grads, state.opt, opt_cfg, frozen)
        if frozen is not None:
            params = model.update_frozen(params, metrics)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return TrainState(params=params, opt=opt, rng=rng), metrics

    return train_step


def make_eval_step(model, remat: bool = False) -> Callable:
    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch, remat=remat)
        return metrics
    return eval_step
