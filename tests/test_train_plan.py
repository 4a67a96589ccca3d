"""Train plans: train-phase segmentation, JSON round-trip, executed
accounting through TrainGovernorExecutor, and the kernel-vs-pass
headline."""
import pytest

from repro.configs import get_config, get_shape
from repro.core import (TRAIN_PHASES, WastePolicy, build_workload, get_chip,
                        pass_level_plan, train_phase_of)
from repro.core.freq import AUTO
from repro.dvfs import (DvfsPlan, StaticPlanGovernor, TrainGovernorExecutor,
                        plan_train_phases)

TAU = 0.006


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gpt3-xl")
    shape = get_shape("paper_gpt3xl")
    chip = get_chip("tpu-v5e")
    plan = plan_train_phases(cfg, chip, shape=shape,
                             policy=WastePolicy(TAU), n_reps=3)
    return cfg, shape, chip, plan


def _executor(plan, chip):
    return TrainGovernorExecutor(StaticPlanGovernor(plan), chip)


def test_train_phase_partition(setup):
    cfg, shape, chip, plan = setup
    kernels = build_workload(cfg, shape, include_optimizer=True)
    phases = {train_phase_of(k) for k in kernels}
    assert phases == set(TRAIN_PHASES)
    # the plan's segments partition the workload exactly, in step order
    assert plan.kind == "train"
    assert plan.segment_names() == list(TRAIN_PHASES)
    assert [s.scope for s in plan.segments] \
        == [f"train-{ph}" for ph in TRAIN_PHASES]
    assert sum(len(s.kernels) for s in plan.segments) == len(kernels)


def test_no_optimizer_drops_opt_phase():
    cfg = get_config("gpt3-xl")
    shape = get_shape("paper_gpt3xl")
    chip = get_chip("tpu-v5e")
    p = plan_train_phases(cfg, chip, shape=shape, n_reps=1,
                          include_optimizer=False)
    assert p.segment_names() == ["fwd", "bwd"]


def test_requires_train_shape():
    cfg = get_config("gpt3-xl")
    chip = get_chip("tpu-v5e")
    from repro.configs.base import ShapeConfig
    dec = ShapeConfig(name="d", seq_len=128, global_batch=4, kind="decode")
    with pytest.raises(ValueError, match="train shape"):
        plan_train_phases(cfg, chip, shape=dec)


def test_bundle_json_roundtrip(setup, tmp_path):
    _, _, _, plan = setup
    path = str(tmp_path / "plan.json")
    plan.save(path)
    p2 = DvfsPlan.load(path)
    assert p2.summary() == plan.summary()
    assert p2.segment_names() == plan.segment_names()
    for a, b in zip(plan.segments, p2.segments):
        assert b.kernel_clock_pairs() == a.kernel_clock_pairs()
        assert b.schedule.n_switches == a.schedule.n_switches


def test_kernel_clock_pairs_dominant(setup):
    _, _, _, plan = setup
    for seg in plan.segments:
        pairs = seg.kernel_clock_pairs()
        assert len(pairs) == len(seg.kernels)
        # every dominant pair actually appears in the schedule (or AUTO
        # for kernels the schedule never covers)
        used = {(e.mem, e.core) for e in seg.schedule.entries}
        for p in pairs:
            assert p in used or p == (AUTO, AUTO)


def test_executor_accounting(setup):
    _, _, chip, plan = setup
    ex = _executor(plan, chip)
    n = 7
    for s in range(n):
        rec = ex.on_step(s)
        assert rec.time_s > 0 and rec.energy_j > 0
    ex.finish()
    summ = ex.summary()
    tot = summ["totals"]
    assert tot["steps"] == n * len(plan.segments)
    # executed plan: saves energy, stays within the (relaxed) time budget
    assert tot["energy_pct"] < -5.0
    assert tot["time_pct"] <= 100 * TAU * 1.2
    # per-step record matches the per-phase planned totals (the meter
    # integrates the noise-free chip model; the plan's meta carries the
    # noisy campaign estimate — they agree to measurement noise)
    step_t = sum(s.schedule.meta["time_s"] for s in plan.segments)
    assert rec.time_s == pytest.approx(step_t, rel=2e-3)


def test_executor_chip_mismatch(setup):
    _, _, _, plan = setup
    with pytest.raises(ValueError, match="planned for"):
        _executor(plan, get_chip("rtx3080ti"))


def test_executor_state_roundtrip(setup):
    """Mid-plan resume: 4 + (serialize/restore) + 3 steps must keep the
    same books as 7 straight steps."""
    _, _, chip, plan = setup
    straight = _executor(plan, chip)
    for s in range(7):
        straight.on_step(s)

    first = _executor(plan, chip)
    for s in range(4):
        first.on_step(s)
    state = first.state_dict()
    resumed = _executor(plan, chip)   # fresh process
    resumed.load_state_dict(state)
    assert resumed.last_step == 3
    for s in range(4, 7):
        resumed.on_step(s)

    a, b = straight.summary()["totals"], resumed.summary()["totals"]
    assert a["steps"] == b["steps"]
    # the restarted chip re-enters the plan from auto clocks, so the books
    # may differ by a couple of boundary switch events — nothing more
    sw_e = 2 * chip.switch_latency_s * 100.0
    assert abs(a["energy_j"] - b["energy_j"]) <= sw_e + 1e-9
    assert abs(a["time_s"] - b["time_s"]) <= 2 * chip.switch_latency_s \
        + 1e-12


def test_kernel_level_beats_pass_level(setup):
    """The paper's headline: same budget, kernel granularity recovers
    strictly more energy than pass granularity (14.6% vs ~2%, §5-6)."""
    cfg, shape, chip, kernel_plan = setup
    pass_plan = plan_train_phases(cfg, chip, shape=shape,
                                  policy=WastePolicy(TAU), n_reps=3,
                                  planner=pass_level_plan)
    assert {s.granularity for s in pass_plan.segments} == {"pass"}

    def executed_energy_pct(plan):
        ex = _executor(plan, chip)
        for s in range(3):
            ex.on_step(s)
        return ex.summary()["totals"]["energy_pct"]

    ek = executed_energy_pct(kernel_plan)
    ep = executed_energy_pct(pass_plan)
    assert ek < ep < 0.5


def test_hlo_calibration():
    """Workload-vs-HLO cross-check: a pure matmul jitted on CPU must
    calibrate to ~1x against the analytic GEMM spec."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core import calibrate_workload_against_hlo
    from repro.core.power_model import KernelSpec
    M = N = K = 64

    def f(a, b):
        return a @ b

    hlo = jax.jit(f).lower(
        jnp.zeros((M, K), jnp.float32),
        jnp.zeros((K, N), jnp.float32)).compile().as_text()
    spec = KernelSpec(name="gemm", kind="gemm", flops=2.0 * M * N * K,
                      hbm_bytes=4.0 * (M * K + K * N + M * N))
    cal = calibrate_workload_against_hlo([spec], hlo)
    assert cal["hlo_flops"] > 0
    assert cal["flops_ratio"] == pytest.approx(1.0, rel=0.05)
