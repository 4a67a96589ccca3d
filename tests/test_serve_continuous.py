"""Continuous-batching engine: parity vs the wave baseline, scheduler
lifecycle, serve phase plans, and executed-energy replay accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, smoke_config
from repro.configs.base import ShapeConfig
from repro.core import (Campaign, WastePolicy, get_chip, global_plan,
                        schedule_from_plan, decode_slot_buckets)
from repro.core.power_model import KernelSpec
from repro.dvfs import (DvfsPlan, ServeGovernorExecutor, StaticPlanGovernor,
                        plan_serve_phases)
from repro.models import build_model
from repro.runtime import EnergyMeter, SimulatedController
from repro.serve import Request, Scheduler, ServeEngine, WaveEngine


@pytest.fixture(scope="module")
def smoke_model():
    cfg = dataclasses.replace(smoke_config(REGISTRY["llama3.2-1b"]),
                              compute_dtype="float32")
    model = build_model(cfg, block_k=16)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def _requests(cfg, n=6, plen=8):
    """Equal prompt lengths (so wave padding is a no-op) with skewed
    generation lengths — slots free and re-admit mid-decode."""
    rng = np.random.default_rng(7)
    news = [3, 11, 2, 7, 5, 9]
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, plen),
                    max_new_tokens=news[i % len(news)]) for i in range(n)]


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_continuous_matches_wave_greedy(smoke_model):
    """Same requests -> identical generated tokens as the wave-based path
    under greedy sampling, even though slots are reused mid-decode."""
    model, params, cfg = smoke_model
    a = ServeEngine(model, params, batch_slots=2,
                    max_seq=64).generate(_requests(cfg))
    b = WaveEngine(model, params, batch_slots=2,
                   max_seq=64).generate(_requests(cfg))
    for x, y in zip(a, b):
        assert x.generated == y.generated, (x.uid, x.generated, y.generated)
    assert all(r.done and r.finished_step is not None for r in a)


@pytest.mark.slow
def test_slot_reuse_happens_mid_decode(smoke_model):
    """With 2 slots and 6 skewed requests the engine must admit into freed
    slots while other sequences are still decoding (not in waves)."""
    model, params, cfg = smoke_model
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64)
    reqs = eng.generate(_requests(cfg))
    # continuous scheduling: strictly fewer decode steps than the wave
    # engine needs for the same workload
    weng = WaveEngine(model, params, batch_slots=2, max_seq=64)
    weng.generate(_requests(cfg))
    assert eng.n_decode_steps < weng.n_decode_steps
    # every slot admitted more than one request over the run
    assert eng.scheduler.n_admitted == len(reqs)
    assert eng.scheduler.n_completed == len(reqs)


@pytest.mark.slow
def test_engine_reset_reproduces(smoke_model):
    model, params, cfg = smoke_model
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64)
    a = [list(r.generated) for r in eng.generate(_requests(cfg))]
    eng.reset()
    b = [list(r.generated) for r in eng.generate(_requests(cfg))]
    assert a == b


# ---------------------------------------------------------------------------
# scheduler lifecycle
# ---------------------------------------------------------------------------

def test_scheduler_slot_lifecycle():
    s = Scheduler(2)
    s.submit(["r0", "r1", "r2"])
    assert s.admit_next() == (0, "r0")
    assert s.admit_next() == (1, "r1")
    assert s.admit_next() is None          # full
    assert s.n_active == 2 and s.pending == 1
    assert s.release(0) == "r0"
    assert s.admit_next() == (0, "r2")     # freed slot is reused
    assert s.pending == 0 and not s.done()
    s.release(0)
    s.release(1)
    assert s.done()
    with pytest.raises(ValueError):
        s.release(1)


def test_decode_slot_buckets():
    assert decode_slot_buckets(1) == [1]
    assert decode_slot_buckets(4) == [1, 2, 4]
    assert decode_slot_buckets(6) == [1, 2, 4, 6]
    assert decode_slot_buckets(16) == [1, 2, 4, 8, 16]


# ---------------------------------------------------------------------------
# serve phase plan + replay accounting
# ---------------------------------------------------------------------------

CHIP = get_chip("tpu-v5e")
PRE = ShapeConfig(name="pre", seq_len=512, global_batch=1, kind="prefill")
DEC = ShapeConfig(name="dec", seq_len=512, global_batch=4, kind="decode")


@pytest.fixture(scope="module")
def serve_plan():
    return plan_serve_phases(REGISTRY["llama3.2-1b"], CHIP, n_slots=4,
                             prefill_shape=PRE, decode_shape=DEC,
                             policy=WastePolicy(0.005), n_reps=10)


def test_bundle_json_roundtrip(serve_plan, tmp_path):
    p = tmp_path / "plan.json"
    serve_plan.save(str(p))
    p2 = DvfsPlan.load(str(p))
    assert p2.chip_name == serve_plan.chip_name
    assert p2.decode_buckets == serve_plan.decode_buckets == [1, 2, 4]
    assert p2.decode_bucket(3) == 4 and p2.decode_bucket(99) == 4
    assert p2.segment_names() == serve_plan.segment_names()
    for a, b in zip(serve_plan.segments, p2.segments):
        assert [dataclasses.asdict(e) for e in b.schedule.entries] == \
            [dataclasses.asdict(e) for e in a.schedule.entries]
        assert b.kernels == a.kernels


def test_replay_energy_matches_plan_prediction(serve_plan):
    """The engine's EnergyMeter totals must match the plan's predicted
    energy_j within tolerance (prediction is off a noisy campaign; the
    meter integrates the noise-free chip model)."""
    for seg in serve_plan.segments:
        meter = EnergyMeter(CHIP, seg.kernels, seg.schedule)
        n = 7
        for i in range(n):
            meter.on_step(i)
        tot = meter.totals()
        predicted = seg.schedule.meta["energy_j"]
        assert predicted > 0
        assert tot["energy_j"] / n == pytest.approx(predicted, rel=0.03), \
            seg.name
        assert tot["time_s"] / n == pytest.approx(
            seg.schedule.meta["time_s"], rel=0.03), seg.name


def test_executed_bundle_saves_energy_within_budget(serve_plan,
                                                    smoke_model):
    """End-to-end replay through the engine: energy savings at <= the
    policy's time budget, per-phase switch counts surfaced."""
    model, params, cfg = smoke_model
    ex = ServeGovernorExecutor(StaticPlanGovernor(serve_plan), CHIP,
                               SimulatedController(CHIP))
    eng = ServeEngine(model, params, batch_slots=4, max_seq=64,
                      executor=ex)
    eng.generate(_requests(cfg, n=8))
    s = eng.energy_summary()
    tot = s["totals"]
    assert tot["energy_j"] < tot["base_energy_j"]          # saves energy
    tau_pct = 100 * serve_plan.meta["tau"]
    assert tot["time_pct"] <= tau_pct + 0.05               # within budget
    assert "n_switches" in tot
    for row in s["phases"].values():                       # per-phase counts
        assert "n_switches" in row
    # prefill ran once per admitted request
    assert s["phases"]["prefill"]["steps"] == 8


def test_energy_meter_kernel_idx_exact():
    """Kernel-name collisions and '+' in names integrate exactly via the
    schedule's kernel indices (the old name-split path dropped them)."""
    kernels = [
        KernelSpec(name="GEMM a+b", kind="gemm", flops=1e12,
                   hbm_bytes=1e9, invocations=2),
        KernelSpec(name="dup", kind="softmax", flops=1e9, hbm_bytes=2e9,
                   invocations=3),
        KernelSpec(name="dup", kind="gelu", flops=2e9, hbm_bytes=1e9,
                   invocations=1),
    ]
    table = Campaign(CHIP, seed=0, n_reps=2).run(kernels)
    plan = global_plan(table, WastePolicy(0.0))
    sched = schedule_from_plan(plan)
    meter = EnergyMeter(CHIP, kernels, sched)
    # manual exact integration off the plan's choices
    from repro.core.freq import ClockPair
    t = e = 0.0
    for i, k in enumerate(kernels):
        pair = table.pairs[int(plan.choice[i])]
        kt, ke = CHIP.evaluate(k, pair)
        t += kt * k.invocations
        e += ke * k.invocations
    t += sched.n_switches * CHIP.switch_latency_s
    e += sched.n_switches * CHIP.switch_latency_s * 100.0
    assert meter._iter_energy == pytest.approx(e, rel=1e-12)
    assert meter._iter_time == pytest.approx(t, rel=1e-12)


def test_prefill_into_slot_preserves_other_slots(smoke_model):
    """Admission writes exactly one batch row of the pooled cache."""
    model, params, cfg = smoke_model
    cache = model.init_cache(3, 32)
    rng = np.random.default_rng(0)
    p0 = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    p1 = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    _, cache = model.prefill_into_slot(params, cache, p0, 1)
    before = jax.tree.map(lambda a: np.asarray(a).copy(), cache)
    _, cache = model.prefill_into_slot(params, cache, p1, 2)
    axes = model.cache_slot_axes()
    for key, ax in axes.items():
        b = np.moveaxis(before[key], ax, 0)
        a = np.moveaxis(np.asarray(cache[key]), ax, 0)
        assert np.array_equal(a[1], b[1]), key      # slot 1 untouched
        assert not np.array_equal(a[2], b[2]), key  # slot 2 overwritten
