"""The unified DVFS governor API: plan IR round-trip + versioning,
governor/controller registries, the phase planners, executor adapters,
and the OnlineGovernor drift -> re-plan -> recovery loop on a synthetic
bucket-mix shift."""
import copy
import json

import pytest

from repro.configs import REGISTRY, get_config, get_shape
from repro.configs.base import ShapeConfig
from repro.core import (Campaign, WastePolicy, WorkloadBuilder,
                        decode_slot_buckets, get_chip, pass_level_plan)
from repro.core.freq import AUTO, ClockPair
from repro.dvfs import (SCHEMA_VERSION, DvfsPlan, DvfsSession,
                        OnlineGovernor, RateLimitedController,
                        ServeGovernorExecutor, StaticPlanGovernor,
                        TrainGovernorExecutor, compile_phase, controller,
                        governor, plan_decode_joint, plan_serve_phases,
                        plan_train_phases, validate_plan_dict)

CHIP = get_chip("tpu-v5e")
TAU = 0.006


SERVE_PRE = ShapeConfig(name="p", seq_len=256, global_batch=1,
                        kind="prefill")
SERVE_DEC = ShapeConfig(name="d", seq_len=256, global_batch=4,
                        kind="decode")


@pytest.fixture(scope="module")
def serve_plan():
    return plan_serve_phases(REGISTRY["llama3.2-1b"], CHIP, n_slots=4,
                             prefill_shape=SERVE_PRE, decode_shape=SERVE_DEC,
                             policy=WastePolicy(TAU), n_reps=3)


@pytest.fixture(scope="module")
def train_plan():
    return plan_train_phases(get_config("gpt3-xl"), CHIP,
                             shape=get_shape("paper_gpt3xl"),
                             policy=WastePolicy(TAU), n_reps=3)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

def test_governor_registry_lookup():
    assert isinstance(governor("kernel-static"), StaticPlanGovernor)
    assert governor("pass-level", aggregation="local").aggregation \
        == "local"
    assert governor("edp", level="pass").level == "pass"
    assert isinstance(governor("online"), OnlineGovernor)


def test_governor_registry_unknown_name():
    with pytest.raises(ValueError, match="unknown governor"):
        governor("thermal-psychic")
    # the error lists the registered names, so typos are self-diagnosing
    with pytest.raises(ValueError, match="kernel-static"):
        governor("nope")


def test_controller_registry():
    assert controller("simulated", CHIP).switch_latency_s \
        == CHIP.switch_latency_s
    assert isinstance(controller("rate-limited", CHIP),
                      RateLimitedController)
    with pytest.raises(ValueError, match="unknown controller"):
        controller("nvml", CHIP)


# ---------------------------------------------------------------------------
# Plan IR: JSON round-trip + versioning + validation
# ---------------------------------------------------------------------------

def test_plan_json_roundtrip(serve_plan):
    plan = serve_plan
    plan2 = DvfsPlan.from_json(plan.to_json())
    assert plan2.schema_version == SCHEMA_VERSION
    assert plan2.kind == "serve"
    assert plan2.segment_names() == plan.segment_names()
    assert plan2.summary() == plan.summary()
    assert plan2.time_s == plan.time_s
    assert plan2.energy_j == plan.energy_j
    for a, b in zip(plan.segments, plan2.segments):
        assert (a.granularity, a.scope, a.bucket) \
            == (b.granularity, b.scope, b.bucket)
        assert a.kernels == b.kernels


def test_plan_rejects_future_schema(serve_plan):
    d = serve_plan.to_dict()
    d["schema_version"] = SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema"):
        DvfsPlan.from_dict(d)
    assert any("newer" in e for e in validate_plan_dict(d))


def test_validate_plan_dict(serve_plan):
    good = serve_plan.to_dict()
    assert validate_plan_dict(good) == []
    bad = copy.deepcopy(good)
    bad["kind"] = "snack"
    bad["segments"][0].pop("kernels")
    bad["segments"][1]["scope"] = "serve-dessert"
    errs = validate_plan_dict(bad)
    assert any("kind" in e for e in errs)
    assert any("kernels" in e for e in errs)
    assert any("scope" in e for e in errs)


def test_ir_tags_and_bucket_lookup(serve_plan):
    plan = serve_plan
    assert plan.segment("prefill").scope == "serve-prefill"
    decode = [s for s in plan.segments if s.scope == "serve-decode"]
    assert [s.bucket for s in decode] == decode_slot_buckets(4)
    assert plan.decode_bucket(3) == 4 and plan.decode_bucket(99) == 4
    assert plan.decode_segment(3).name == "decode@4"


def test_from_json_rejects_a_pre_ir_bundle(train_plan):
    """A document without ``segments`` (the retired bundle layout, or any
    other non-plan) is refused with a ValueError, not a KeyError."""
    legacy = json.dumps({
        "chip": train_plan.chip_name,
        "meta": train_plan.meta,
        "phases": {s.name: s.to_dict() for s in train_plan.segments},
    })
    with pytest.raises(ValueError, match="segments"):
        DvfsPlan.from_json(legacy)
    with pytest.raises(ValueError, match="segments"):
        DvfsPlan.from_json("[]")


# ---------------------------------------------------------------------------
# Phase planners
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("planner,granularity",
                         [(None, "kernel"), (pass_level_plan, "pass")])
def test_plan_serve_phases_segments(serve_plan, planner, granularity):
    """Prefill first, then one serve-decode segment per slot bucket in
    ascending order; granularity follows the planner."""
    plan = serve_plan if planner is None else plan_serve_phases(
        REGISTRY["llama3.2-1b"], CHIP, n_slots=4, prefill_shape=SERVE_PRE,
        decode_shape=SERVE_DEC, policy=WastePolicy(TAU), n_reps=2,
        planner=planner)
    assert plan.kind == "serve"
    assert [s.scope for s in plan.segments] \
        == ["serve-prefill"] + ["serve-decode"] * 3
    assert [s.bucket for s in plan.segments[1:]] == decode_slot_buckets(4)
    assert plan.segment_names() == ["prefill", "decode@1", "decode@2",
                                    "decode@4"]
    assert {s.granularity for s in plan.segments} == {granularity}


# ---------------------------------------------------------------------------
# Executor adapters and controllers
# ---------------------------------------------------------------------------

def test_executor_state_dict_survives_replan_carry(train_plan):
    """Books flushed into the carry by a mid-run plan adoption must
    survive checkpoint-restart, not just the current-revision counts."""
    gov = StaticPlanGovernor(train_plan)
    ex = TrainGovernorExecutor(gov, CHIP)
    for s in range(3):
        ex.on_step(s)
    gov.adopt(DvfsPlan.from_json(train_plan.to_json()), reason="swap")
    for s in range(3, 5):
        ex.on_step(s)                  # flushes pre-adopt books to carry
    resumed = TrainGovernorExecutor(StaticPlanGovernor(train_plan), CHIP)
    resumed.load_state_dict(ex.state_dict())
    a, b = ex.summary()["totals"], resumed.summary()["totals"]
    assert a["steps"] == b["steps"] == 15        # 5 steps x 3 phases
    assert abs(a["energy_j"] - b["energy_j"]) < 1e-9
    assert abs(a["time_s"] - b["time_s"]) < 1e-9


def test_executor_rejects_wrong_chip(train_plan):
    gov = StaticPlanGovernor(train_plan)
    with pytest.raises(ValueError, match="planned for"):
        TrainGovernorExecutor(gov, get_chip("rtx3080ti"))


def test_rate_limited_controller_quantizes_and_throttles():
    ctl = RateLimitedController(CHIP, min_interval_s=1.0)
    grid = CHIP.grid
    # off-grid request snaps to the nearest table entry
    ctl.set_clocks(ClockPair(grid.mem_clocks_mhz[0] + 7.0,
                             grid.core_clocks_mhz[0] + 11.0))
    assert ctl.current == ClockPair(grid.mem_clocks_mhz[0],
                                    grid.core_clocks_mhz[0])
    assert ctl.n_quantized == 2 and ctl.n_switches == 1
    # a second switch inside the interval is refused: clocks stay put
    ctl.set_clocks(ClockPair(grid.mem_clocks_mhz[1],
                             grid.core_clocks_mhz[1]))
    assert ctl.n_throttled == 1 and ctl.n_switches == 1
    ctl.advance(2.0)          # modeled time passes the interval
    ctl.set_clocks(ClockPair(grid.mem_clocks_mhz[1],
                             grid.core_clocks_mhz[1]))
    assert ctl.n_switches == 2
    ctl.reset()               # release always succeeds
    assert ctl.current == ClockPair(AUTO, AUTO)


def test_rate_limited_executor_realizes_fewer_switches(train_plan):
    free = TrainGovernorExecutor(StaticPlanGovernor(train_plan), CHIP)
    lim = TrainGovernorExecutor(
        StaticPlanGovernor(train_plan), CHIP,
        controller=RateLimitedController(CHIP, min_interval_s=1e-2))
    for s in range(3):
        free.on_step(s), lim.on_step(s)
    n_free = free.summary()["totals"]["n_switches"]
    n_lim = lim.summary()["totals"]["n_switches"]
    assert n_lim < n_free
    assert lim.summary()["n_throttled"] > 0


# ---------------------------------------------------------------------------
# DvfsSession facade
# ---------------------------------------------------------------------------

def _assert_same_segments(a: DvfsPlan, b: DvfsPlan) -> None:
    assert a.kind == b.kind and a.chip_name == b.chip_name
    assert [s.to_dict() for s in a.segments] \
        == [s.to_dict() for s in b.segments]


def test_session_train_equals_plan_train_phases(train_plan):
    with DvfsSession(chip=CHIP, tau=TAU, n_reps=3) as sess:
        plan = sess.plan_train(get_config("gpt3-xl"),
                               shape=get_shape("paper_gpt3xl"))
        ex = sess.train_executor()
        for s in range(3):
            ex.on_step(s)
        report = sess.report()
    # same campaign seed + planner => identical segments; the session
    # only stamps the governor's name into the meta
    _assert_same_segments(plan, train_plan)
    assert {k: v for k, v in plan.meta.items() if k != "governor"} \
        == train_plan.meta
    assert report["governor"] == plan.meta["governor"] == "kernel-static"
    assert report["executed"][0]["totals"]["steps"] == 9
    assert report["plan"]["phases"].keys() \
        == train_plan.summary()["phases"].keys()


def test_session_serve_equals_plan_serve_phases(serve_plan):
    with DvfsSession(chip=CHIP, tau=TAU, n_reps=3) as sess:
        plan = sess.plan_serve(REGISTRY["llama3.2-1b"], n_slots=4,
                               prefill_shape=SERVE_PRE,
                               decode_shape=SERVE_DEC)
    _assert_same_segments(plan, serve_plan)
    added = {"governor", "decode_mix", "n_slots"}
    assert {k: v for k, v in plan.meta.items() if k not in added} \
        == {k: v for k, v in serve_plan.meta.items() if k not in added}
    assert plan.meta["governor"] == "kernel-static"
    assert plan.meta["decode_mix"] == {1: 1 / 3, 2: 1 / 3, 4: 1 / 3}


def test_session_governor_kwargs_and_exclusive_policy():
    with pytest.raises(ValueError, match="not both"):
        DvfsSession(policy=WastePolicy(0.0), tau=0.1)
    sess = DvfsSession(governor="pass-level", aggregation="local")
    assert sess.governor.aggregation == "local"


def test_static_local_aggregation_reaches_phase_path(train_plan):
    """aggregation='local' must shape plan_train/plan_serve, not just
    solve(): the compiled phases carry the local per-kernel planner."""
    with DvfsSession(chip=CHIP, tau=TAU, n_reps=3,
                     aggregation="local") as sess:
        plan = sess.plan_train(get_config("gpt3-xl"),
                               shape=get_shape("paper_gpt3xl"))
    for seg in plan.segments:
        assert seg.schedule.meta["plan"] == "kernel-local"
    # and the default (global) still compiles switch-aware coalesced
    assert train_plan.segment("fwd").schedule.meta["plan"] \
        == "coalesced-global"


def test_session_online_governor_end_to_end():
    """governor='online' by name: the session wires chip + a fresh
    decode-table provider, so a drift-triggered re-plan on the serving
    hot path works instead of raising."""
    cfg = REGISTRY["llama3.2-1b"]
    pre = ShapeConfig(name="p", seq_len=256, global_batch=1,
                      kind="prefill")
    dec = ShapeConfig(name="d", seq_len=256, global_batch=4, kind="decode")
    with DvfsSession(chip=CHIP, tau=0.01, n_reps=2, governor="online",
                     window=16, mix_threshold=0.2) as sess:
        sess.plan_serve(cfg, n_slots=4, prefill_shape=pre,
                        decode_shape=dec)
        ex = sess.serve_executor()
        for _ in range(20):          # first window -> reference mix
            ex.on_decode(4)
        for _ in range(40):          # drifted traffic
            ex.on_decode(1)
        report = sess.report()
    assert sess.governor.revision > 2      # plan_serve adopt + replan
    assert report["governor_events"]
    assert report["executed"][0]["totals"]["steps"] == 60


def test_online_governor_adopt_anchors_reference_mix(decode_tables):
    """A plan adopted after construction (e.g. loaded from disk) must
    bring its recorded decode_mix along as the drift reference."""
    policy = WastePolicy(0.01)
    gov = OnlineGovernor(policy=policy, chip=CHIP, tables=decode_tables,
                         window=16)
    plan = DvfsPlan.from_json(
        _serve_plan(decode_tables, PLANNED_MIX, policy).to_json())
    gov.adopt(plan)
    tot = sum(PLANNED_MIX.values())
    assert gov._ref_mix == {b: f / tot for b, f in PLANNED_MIX.items()}
    # already-drifted traffic is then caught within one window
    ex = ServeGovernorExecutor(gov, CHIP)
    for _ in range(20):
        ex.on_decode(2)
    assert any(any(r.startswith("mix-drift") for r in e["reason"])
               for e in gov.events if "reason" in e)


# ---------------------------------------------------------------------------
# OnlineGovernor: drift detection -> joint re-plan -> energy recovery
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def decode_tables():
    cfg = REGISTRY["llama3.2-1b"]
    dec = ShapeConfig(name="d", seq_len=512, global_batch=4, kind="decode")
    camp = Campaign(CHIP, seed=0, n_reps=3)
    return {b: camp.run(WorkloadBuilder(cfg, dec, batch_override=b).build())
            for b in decode_slot_buckets(4)}


def _serve_plan(decode_tables, mix, policy):
    segs = plan_decode_joint(decode_tables, mix, CHIP, policy)
    prefill = compile_phase(decode_tables[1], "prefill", CHIP, policy,
                            scope="serve-prefill")
    return DvfsPlan(chip_name=CHIP.name, kind="serve",
                    segments=[prefill] + segs,
                    meta={"decode_mix": dict(mix)})


# deterministic drifted traffic: a 16-step pattern whose empirical mix
# {1: 2/16, 2: 13/16, 4: 1/16} sits far (TV ~ 0.51) from the planned mix
# below, concentrated on the bucket the stale plan gave the least slack —
# so the stale plan under-spends the aggregate budget and strands energy
PLANNED_MIX = {1: 0.30, 2: 0.30, 4: 0.40}
DRIFT_PATTERN = [1] * 2 + [2] * 13 + [4]
DRIFT_MIX = {1: 2 / 16, 2: 13 / 16, 4: 1 / 16}
WINDOW = 32
N_STEPS = 10 * WINDOW


def _drive(executor, n=N_STEPS):
    for i in range(n):
        executor.on_decode(DRIFT_PATTERN[i % len(DRIFT_PATTERN)])
    executor.finish()
    return executor.summary()["totals"]


def test_online_governor_replans_on_mix_shift(decode_tables):
    policy = WastePolicy(0.01)
    plan = _serve_plan(decode_tables, PLANNED_MIX, policy)
    stale_sched = {s.name: s.schedule.to_json() for s in plan.segments}
    gov = OnlineGovernor(plan, policy=policy, chip=CHIP,
                         tables=decode_tables, window=WINDOW)
    ex = ServeGovernorExecutor(gov, CHIP)
    online = _drive(ex)

    # drift was detected and a re-plan adopted
    assert gov.revision > 1
    assert any(any(r.startswith("mix-drift") for r in e["reason"])
               for e in gov.events if "reason" in e)
    # decode segments were actually re-planned; prefill untouched
    assert gov.plan.segment("prefill").schedule.to_json() \
        == stale_sched["prefill"]
    assert any(gov.plan.segment(n).schedule.to_json() != stale_sched[n]
               for n in stale_sched if n.startswith("decode@"))
    # the executor carried pre-replan books across the meter swap
    assert online["steps"] == N_STEPS
    assert ex.summary().get("governor_revision") == gov.revision

    # -- energy recovery vs the stale plan and the oracle ----------------
    stale = ServeGovernorExecutor(StaticPlanGovernor(
        _serve_plan(decode_tables, PLANNED_MIX, policy)), CHIP)
    oracle = ServeGovernorExecutor(StaticPlanGovernor(
        _serve_plan(decode_tables, DRIFT_MIX, policy)), CHIP)
    stale_tot = _drive(stale)
    oracle_tot = _drive(oracle)

    gap = stale_tot["energy_j"] - oracle_tot["energy_j"]
    assert gap > 0, "drift must leave a real energy gap to recover"
    recovered = stale_tot["energy_j"] - online["energy_j"]
    assert recovered >= 0.5 * gap, \
        f"recovered {recovered:.3f} J of a {gap:.3f} J gap"
    # and the re-planned operating point respects the planned time budget
    # (phase-boundary switches observed at the controller are accounted
    # on top, as in every executor summary)
    t_fresh = sum(DRIFT_MIX[s.bucket] * s.time_s
                  for s in gov.plan.segments if s.bucket is not None)
    t_base = sum(DRIFT_MIX[b] * decode_tables[b].baseline_totals()[0]
                 for b in DRIFT_MIX)
    assert t_fresh <= (1 + policy.tau) * t_base * (1 + 1e-6)


def test_online_governor_perf_drift_channel(decode_tables):
    """Measured-vs-planned deviation (hardware counters disagreeing with
    the plan) also triggers a re-plan, via the executor's measure_fn."""
    policy = WastePolicy(0.01)
    plan = _serve_plan(decode_tables, PLANNED_MIX, policy)
    gov = OnlineGovernor(plan, policy=policy, chip=CHIP,
                         tables=decode_tables, window=WINDOW,
                         perf_threshold=0.02, min_perf_obs=4)
    seg = plan.segment("decode@4")
    # counters read 8% hotter than planned
    ex = ServeGovernorExecutor(
        gov, CHIP, measure_fn=lambda name: (
            gov.plan.segment(name).time_s * 1.08,
            gov.plan.segment(name).energy_j * 1.08))
    for _ in range(8):
        ex.on_decode(4)
    assert gov.revision > 1
    assert any(any(r.startswith("perf-drift") for r in e["reason"])
               for e in gov.events if "reason" in e)


def test_renamed_prefill_round_trips_and_executes(decode_tables):
    """Prefill segments are found by scope, not by the name 'prefill' —
    a plan with a custom prefill name must save/load and execute."""
    policy = WastePolicy(0.01)
    plan = DvfsPlan(chip_name=CHIP.name, kind="serve", segments=[
        compile_phase(decode_tables[1], "prefill_ctx", CHIP, policy,
                      scope="serve-prefill"),
        compile_phase(decode_tables[1], "decode@1", CHIP, policy,
                      scope="serve-decode", bucket=1)])
    p2 = DvfsPlan.from_json(plan.to_json())
    assert p2.prefill_segment().name == "prefill_ctx"
    ex = ServeGovernorExecutor(StaticPlanGovernor(p2), CHIP)
    ex.on_prefill()
    ex.finish()
    assert ex.summary()["phases"]["prefill_ctx"]["steps"] == 1


def test_online_prefill_perf_drift_does_not_loop(decode_tables):
    """Perf drift on a segment replan() cannot rebuild (prefill) must
    not trigger endless decode re-measurement — it is surfaced once."""
    policy = WastePolicy(0.01)
    gov = OnlineGovernor(_serve_plan(decode_tables, PLANNED_MIX, policy),
                         policy=policy, chip=CHIP, tables=decode_tables,
                         window=8, perf_threshold=0.02, min_perf_obs=2)
    ex = ServeGovernorExecutor(
        gov, CHIP, measure_fn=lambda n: (
            gov.plan.segment(n).time_s * 1.05,
            gov.plan.segment(n).energy_j * 1.05))
    for _ in range(6):
        ex.on_prefill()
    assert gov.revision == 1          # no decode re-plan fired
    noted = [e for e in gov.events if e.get("replan") == "no-target"]
    assert len(noted) == 1            # surfaced exactly once


def test_online_governor_degrades_without_tables(decode_tables):
    """Drift on a plan with no tables wired (e.g. adopted from disk into
    a bare governor) must not raise out of the serving hot path — it
    records the unactionable drift and keeps serving the stale plan."""
    policy = WastePolicy(0.01)
    gov = OnlineGovernor(policy=policy, chip=CHIP, window=8)
    gov.adopt(_serve_plan(decode_tables, PLANNED_MIX, policy))
    ex = ServeGovernorExecutor(gov, CHIP)
    for _ in range(12):
        ex.on_decode(2)               # drifted vs the planned mix
    assert gov.revision == 1          # no re-plan happened...
    assert any(e.get("replan") == "unavailable" for e in gov.events)
    assert ex.summary()["totals"]["steps"] == 12


def test_plan_decode_joint_respects_aggregate_budget(decode_tables):
    policy = WastePolicy(0.01)
    for mix in (PLANNED_MIX, DRIFT_MIX):
        segs = {s.bucket: s for s in
                plan_decode_joint(decode_tables, mix, CHIP, policy)}
        t = sum(mix[b] * segs[b].time_s for b in mix)
        t_base = sum(mix[b] * decode_tables[b].baseline_totals()[0]
                     for b in mix)
        assert t <= (1 + policy.tau) * t_base * (1 + 1e-6)


def test_rate_limited_controller_honors_interval_across_replan(
        decode_tables):
    """Satellite: an online re-plan (revision bump) landing mid-throttle-
    window must not let the swapped-in schedule emit switches faster than
    the driver's min_interval, nor at off-grid frequencies."""
    policy = WastePolicy(0.01)
    plan = _serve_plan(decode_tables, PLANNED_MIX, policy)
    gov = OnlineGovernor(plan, policy=policy, chip=CHIP,
                         tables=decode_tables, window=WINDOW)

    class RecordingController(RateLimitedController):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.applied = []            # (modeled time, applied pair)

        def set_clocks(self, pair):
            n0 = self.n_switches
            super().set_clocks(pair)
            if self.n_switches > n0:
                self.applied.append((self._t, self.current))

    min_interval = 2e-3
    ctl = RecordingController(CHIP, min_interval_s=min_interval)
    ex = ServeGovernorExecutor(gov, CHIP, controller=ctl)
    for i in range(30):
        ex.on_decode(DRIFT_PATTERN[i % len(DRIFT_PATTERN)])
    # force a revision bump mid-stream: the throttle window straddles it
    rev0 = gov.revision
    gov.replan(DRIFT_MIX, reasons=["forced:test"])
    assert gov.revision == rev0 + 1
    for i in range(30):
        ex.on_decode(DRIFT_PATTERN[i % len(DRIFT_PATTERN)])
    summary = ex.summary()
    ex.finish()

    # every *applied* switch respects the driver interval, re-plan or not
    times = [t for t, _ in ctl.applied]
    assert len(times) >= 2
    assert all(b - a >= min_interval - 1e-12
               for a, b in zip(times, times[1:]))
    # the driver refused some requests (the schedule asked faster)
    assert ctl.n_throttled > 0
    # nothing the new schedule requested bypassed step quantization
    grid = CHIP.grid
    for _, pair in ctl.applied:
        assert pair.mem == AUTO or pair.mem in grid.mem_clocks_mhz
        assert pair.core == AUTO or pair.core in grid.core_clocks_mhz
    # accounting survived the mid-window swap: all 60 steps in the books
    assert summary["totals"]["steps"] == 60
    assert summary["governor_revision"] == gov.revision
