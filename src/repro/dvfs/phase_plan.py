"""Per-phase DVFS planning: the offline planners that emit a
:class:`~repro.dvfs.plan_ir.DvfsPlan` for the serving and the training
path.

**Serving** (:func:`plan_serve_phases`).  A serving step is either a
*prefill* (one admitted prompt) or a *decode* step over the currently
active slots.  The two phases sit at opposite ends of the roofline —
prefill is GEMM/compute-heavy, decode is HBM-bound weight/KV streaming
(paper §10–11) — so they get separate segments.  Decode additionally
varies with how many slots are occupied, so the plan carries one
``serve-decode`` segment per active-slot-count *bucket* (powers of two,
see :func:`~repro.core.workload.decode_slot_buckets`).

**Training** (:func:`plan_train_phases`).  One optimizer step decomposes
into three kernel phases executed back-to-back every step
(:data:`~repro.core.workload.TRAIN_PHASES`):

* ``fwd``  — embedding, forward layers, and the loss head (including the
  lm-head backward GEMMs the workload builder tags ``loss``; they run
  contiguously at the fwd/bwd boundary, so either side is switch-neutral),
* ``bwd``  — the backward pass proper,
* ``opt``  — the optimizer update (paper beyond-§5 extension).

Each phase carries its own switch-cost-aware schedule planned against the
phase's share of the measurement table (the paper's headline claim: a
per-*kernel* plan recovers 14.6 % of training energy where a per-*pass*
plan recovers ~2 %, §5–6).  The train-phase lifecycle is::

    plan_train_phases()            offline: decompose -> measure -> plan
        -> DvfsPlan.save()         ship JSON to the training job
        -> TrainGovernorExecutor   online: replay fwd|bwd|opt clocks
           .on_step(step)          around every Trainer step, meter energy
        -> state_dict()/load_      survive checkpoint-restart mid-plan

:class:`~repro.dvfs.session.DvfsSession` is the usual caller; it picks
the planner from its governor.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

from ..configs.base import ModelConfig, ShapeConfig
from ..core.coalesce import coalesced_global_plan
from ..core.measure import Campaign, MeasurementTable
from ..core.objectives import WastePolicy
from ..core.planner import Plan
from ..core.power_model import Chip, KernelSpec
from ..core.schedule import schedule_from_coalesced, schedule_from_plan
from ..core.workload import (TRAIN_PHASES, WorkloadBuilder,
                             calibrate_workload_against_hlo,
                             decode_slot_buckets, train_phase_of)
from .plan_ir import DvfsPlan, PlanSegment, _granularity_from_meta


def compile_phase(table: MeasurementTable, name: str, chip: Chip,
                  policy: Optional[WastePolicy] = None,
                  planner: Optional[Callable[..., Plan]] = None, *,
                  scope: str, bucket: Optional[int] = None
                  ) -> PlanSegment:
    """Compile one phase's measurement table into a deployable segment.

    By default the phase is planned with
    :func:`~repro.core.coalesce.coalesced_global_plan`, which charges clock
    switches against the time budget directly.  Pass a ``planner`` (e.g.
    :func:`~repro.core.planner.global_plan`) to use a switch-oblivious
    kernel-level plan instead; its budget is then shrunk by the realized
    switch overhead and re-planned so the *executed* phase still meets the
    policy.  The segment's ``granularity`` follows the planner recorded in
    the schedule's meta.
    """
    policy = policy if policy is not None else WastePolicy()
    if planner is None:
        cp = coalesced_global_plan(
            table, policy, switch_latency_s=chip.switch_latency_s)
        sched = schedule_from_coalesced(cp, meta={"phase": name})
    else:
        plan = planner(table, policy)
        sched = schedule_from_plan(plan, meta={"phase": name})
        # switch-oblivious planner: shrink the budget by the realized
        # switch overhead and re-plan (two rounds converge — switch counts
        # only move when the plan does)
        t_base, _ = table.baseline_totals()
        for _ in range(2):
            overhead = sched.n_switches * chip.switch_latency_s
            eff_tau = policy.tau - overhead / t_base
            plan = planner(table, WastePolicy(eff_tau))
            sched = schedule_from_plan(plan, meta={"phase": name})
    return PlanSegment(name=name, schedule=sched, kernels=table.kernels,
                       granularity=_granularity_from_meta(sched.meta),
                       scope=scope, bucket=bucket)


def plan_serve_phases(cfg: ModelConfig, chip: Chip, *,
                      n_slots: int,
                      prefill_shape: ShapeConfig,
                      decode_shape: ShapeConfig,
                      policy: Optional[WastePolicy] = None,
                      planner: Optional[Callable[..., Plan]] = None,
                      seed: int = 0, n_reps: int = 5,
                      tp: int = 1, dp: int = 1,
                      kv_dtype: Optional[str] = None,
                      meta: Optional[Dict] = None) -> DvfsPlan:
    """Measure + plan every serving phase of (cfg, shapes) on ``chip``.

    Runs one simulated measurement campaign per phase (prefill at the
    prefill shape's batch, decode once per slot bucket with the bucket as
    the batch) and compiles each into a segment: ``prefill`` first, then
    ``decode@<b>`` for each bucket in ascending order.  ``kv_dtype``
    (e.g. ``"int8"``) plans the decode buckets against the quantized page
    pool's workload model — the cache-read stream at its stored width — so
    the plan tracks the shifted decode roofline.

    By default phases are planned with
    :func:`~repro.core.coalesce.coalesced_global_plan`, which charges clock
    switches against the time budget directly — decode steps are short
    (ms), so even µs-scale switches are budget-relevant there.  Pass a
    ``planner`` (e.g. :func:`~repro.core.planner.global_plan`) to use a
    switch-oblivious kernel-level plan instead (see :func:`compile_phase`).
    """
    policy = policy if policy is not None else WastePolicy()
    camp = Campaign(chip, seed=seed, n_reps=n_reps)

    def plan_one(name: str, kernels: List[KernelSpec], scope: str,
                 bucket: Optional[int] = None) -> PlanSegment:
        return compile_phase(camp.run(kernels), name, chip, policy, planner,
                             scope=scope, bucket=bucket)

    pre_kernels = WorkloadBuilder(cfg, prefill_shape, tp=tp, dp=dp,
                                  kv_dtype=kv_dtype).build()
    segments = [plan_one("prefill", pre_kernels, "serve-prefill")]
    for b in decode_slot_buckets(n_slots):
        kernels = WorkloadBuilder(cfg, decode_shape, tp=tp, dp=dp,
                                  batch_override=b,
                                  kv_dtype=kv_dtype).build()
        segments.append(plan_one(f"decode@{b}", kernels, "serve-decode", b))
    md = dict(meta or {})
    md.update({"model": cfg.name, "tau": policy.tau, "n_slots": n_slots,
               "prefill_shape": prefill_shape.name,
               "decode_shape": decode_shape.name,
               "kv_dtype": kv_dtype or "none"})
    return DvfsPlan(chip_name=chip.name, kind="serve", segments=segments,
                    meta=md)


def plan_train_phases(cfg: ModelConfig, chip: Chip, *,
                      shape: ShapeConfig,
                      policy: Optional[WastePolicy] = None,
                      planner: Optional[Callable[..., Plan]] = None,
                      seed: int = 0, n_reps: int = 5,
                      tp: int = 1, dp: int = 1,
                      include_optimizer: bool = True,
                      hlo_text: Optional[str] = None,
                      table: Optional[MeasurementTable] = None,
                      meta: Optional[Dict] = None) -> DvfsPlan:
    """Measure + plan the fwd/bwd/opt phases of one train step on ``chip``.

    Runs a single measurement campaign over the full train-step workload
    (so kernel-level and pass-level comparisons share one table), then
    plans each train phase on its subset of the table, emitting the
    ``train-fwd``, ``train-bwd`` and ``train-opt`` segments in that order.
    ``dp``/``tp`` give the per-device shard: the per-device batch is
    ``shape.global_batch // dp`` and tensor-parallel kernels are sharded
    ``tp`` ways, exactly as
    :class:`~repro.core.workload.WorkloadBuilder` does.  Pass the jitted
    step's optimized HLO as ``hlo_text`` to record an analytic-vs-compiled
    calibration in the plan meta.  Pass a precomputed ``table`` (whose
    kernels must be this same workload) to plan several times — e.g.
    kernel- vs pass-level, or transferred vs replanned — against one
    measurement campaign instead of re-measuring.
    """
    policy = policy if policy is not None else WastePolicy()
    if shape.kind != "train":
        raise ValueError(f"train shape required, got kind={shape.kind!r}")
    if table is None:
        kernels = WorkloadBuilder(
            cfg, shape, tp=tp, dp=dp,
            include_optimizer=include_optimizer).build()
        table = Campaign(chip, seed=seed, n_reps=n_reps).run(kernels)
    else:
        kernels = table.kernels
    segments: List[PlanSegment] = []
    for ph in TRAIN_PHASES:
        mask = [train_phase_of(k) == ph for k in kernels]
        if not any(mask):
            continue
        segments.append(compile_phase(table.subset(mask), ph, chip, policy,
                                      planner, scope=f"train-{ph}"))
    md = dict(meta or {})
    md.update({"model": cfg.name, "tau": policy.tau, "shape": shape.name,
               "seq_len": shape.seq_len, "global_batch": shape.global_batch,
               "tp": tp, "dp": dp,
               "include_optimizer": include_optimizer})
    if hlo_text is not None:
        md["hlo_calibration"] = calibrate_workload_against_hlo(
            kernels, hlo_text)
    return DvfsPlan(chip_name=chip.name, kind="train", segments=segments,
                    meta=md)
