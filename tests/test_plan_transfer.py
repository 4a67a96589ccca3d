"""DP/TP plan transfer: per-kernel choice invariance under mesh
rescaling, energy parity vs per-mesh replanning, and FT-restart mid-plan
resume of the executed plan."""
import pytest

from repro.configs import get_config, get_shape
from repro.core import WastePolicy, get_chip
from repro.core.freq import AUTO
from repro.dvfs import (DvfsPlan, StaticPlanGovernor, TrainGovernorExecutor,
                        plan_train_phases)
from repro.launch.mesh import MeshSpec
from repro.parallel import compare_transfer, transfer_train_plan

TAU = 0.006


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gpt3-xl")
    shape = get_shape("paper_gpt3xl")
    chip = get_chip("tpu-v5e")
    src = plan_train_phases(cfg, chip, shape=shape,
                            policy=WastePolicy(TAU), n_reps=3)
    return cfg, shape, chip, src


def test_mesh_spec():
    spec = MeshSpec(dp=4, tp=2, pod=2)
    assert spec.n_devices == 16
    assert spec.data_extent == 8
    assert spec.describe() == "dp8_tp2_pod2"
    with pytest.raises(ValueError):
        MeshSpec(dp=0)


def test_mesh_spec_from_mesh():
    jax = pytest.importorskip("jax")
    from repro.launch.mesh import make_host_mesh
    spec = MeshSpec.from_mesh(make_host_mesh(1, 1))
    assert (spec.dp, spec.tp, spec.pod) == (1, 1, 1)


def test_dp_choice_invariance(setup):
    """Mesh rescaling must leave per-kernel clock choices invariant for
    every kernel whose roofline position is unchanged (|log AI shift|
    within the name-preference band); only kernels that genuinely moved
    (e.g. the lm-head GEMM, whose contraction dim is per-device tokens)
    may remap."""
    import math
    from repro.core.workload import WorkloadBuilder
    from repro.parallel.plan_transfer import NAME_PREF_LOG_AI
    cfg, shape, chip, src = setup
    for dp in (2, 4):
        xfer = transfer_train_plan(src, cfg, chip, shape,
                                   MeshSpec(dp=dp), n_reps=3)
        for ph in src.segment_names():
            s_seg, x_seg = src.segment(ph), xfer.segment(ph)
            assert x_seg.schedule.meta["n_unmatched"] == 0
            src_ai = {k.name: k.arithmetic_intensity for k in s_seg.kernels}
            src_pairs = dict(zip((k.name for k in s_seg.kernels),
                                 s_seg.kernel_clock_pairs()))
            x_pairs = dict(zip((k.name for k in x_seg.kernels),
                               x_seg.kernel_clock_pairs()))
            n_stable = 0
            for k in x_seg.kernels:
                shift = abs(math.log(max(k.arithmetic_intensity, 1e-9))
                            - math.log(max(src_ai[k.name], 1e-9)))
                if shift <= NAME_PREF_LOG_AI:
                    assert x_pairs[k.name] == src_pairs[k.name], \
                        (dp, ph, k.name)
                    n_stable += 1
            assert n_stable >= len(x_seg.kernels) - 1
    # at dp=2 nothing moves: the transfer is a verbatim replay
    xfer2 = transfer_train_plan(src, cfg, chip, shape, MeshSpec(dp=2),
                                n_reps=3)
    assert all(seg.schedule.meta["n_remapped"] == 0
               for seg in xfer2.segments)


def test_tp_transfer_remaps_along_roofline(setup):
    """TP sharding cuts GEMM arithmetic intensity ~tp-fold; the transfer
    must remap at least some kernels instead of replaying stale clocks."""
    cfg, shape, chip, src = setup
    xfer = transfer_train_plan(src, cfg, chip, shape, MeshSpec(tp=4),
                               n_reps=3)
    remapped = sum(seg.schedule.meta["n_remapped"]
                   for seg in xfer.segments)
    assert remapped > 0


def test_transfer_energy_parity(setup):
    """Acceptance: the single-device plan replayed under DP and TP meshes
    stays within 2% of the per-mesh replanned energy, within the time
    budget."""
    cfg, shape, chip, src = setup
    specs = [MeshSpec(dp=2), MeshSpec(dp=4), MeshSpec(tp=2),
             MeshSpec(tp=4)]
    rows = compare_transfer(src, cfg, chip, shape, specs,
                            WastePolicy(TAU), n_reps=3)
    for r in rows:
        assert abs(r.energy_vs_replan_pct) <= 2.0, r.mesh
        assert r.transfer_time_pct <= 1.0, r.mesh
        assert r.transfer_energy_pct < -5.0, r.mesh


def test_unmatched_collectives_fall_back_to_auto(setup):
    """Kernels that exist only in the sharded workload (TP collectives)
    were never measured by the source campaign -> auto clocks."""
    cfg, shape, chip, src = setup
    xfer = transfer_train_plan(src, cfg, chip, shape, MeshSpec(tp=2),
                               n_reps=2, include_comm=True)
    n_unmatched = 0
    for seg in xfer.segments:
        n_unmatched += seg.schedule.meta["n_unmatched"]
        pairs = dict(zip((k.name for k in seg.kernels),
                         seg.kernel_clock_pairs()))
        for name, pair in pairs.items():
            if "AllReduce" in name:
                assert pair == (AUTO, AUTO)
    assert n_unmatched > 0


def test_transferred_bundle_executes(setup):
    """A transferred plan is a first-class train DvfsPlan: it replays
    through the executor with per-shard accounting."""
    cfg, shape, chip, src = setup
    xfer = transfer_train_plan(src, cfg, chip, shape, MeshSpec(dp=2),
                               n_reps=3)
    ex = TrainGovernorExecutor(StaticPlanGovernor(xfer), chip)
    for s in range(3):
        ex.on_step(s)
    tot = ex.summary()["totals"]
    assert tot["energy_pct"] < -5.0


@pytest.mark.slow
def test_ft_restart_mid_plan_resume(tmp_path):
    """FT drill: an injected failure mid-run restarts the Trainer from
    the latest checkpoint; the executor's energy books must resume from
    the checkpointed state and end with exactly one record per committed
    step — identical totals to a failure-free run."""
    import dataclasses
    import jax
    from repro.configs import REGISTRY, smoke_config
    from repro.ckpt import CheckpointManager
    from repro.data import DataPipeline
    from repro.models import build_model
    from repro.runtime import FailureInjector
    from repro.train import OptimizerConfig, make_train_step
    from repro.train.loop import Trainer, TrainerConfig

    chip = get_chip("tpu-v5e")
    full = get_config("gpt3-xl")
    shape = get_shape("paper_gpt3xl")
    plan = plan_train_phases(full, chip, shape=shape,
                             policy=WastePolicy(TAU), n_reps=2)

    def run(workdir, fail_at):
        cfg = smoke_config(REGISTRY["gpt3-xl"])
        model = build_model(cfg, block_k=16)
        step = make_train_step(model, OptimizerConfig(lr=1e-2,
                                                      warmup_steps=2,
                                                      decay_steps=100))
        pipeline = DataPipeline(vocab_size=cfg.vocab_size,
                                batch_per_host=4, seq_len=32)
        ex = TrainGovernorExecutor(StaticPlanGovernor(plan), chip)
        trainer = Trainer(model, step, pipeline,
                          CheckpointManager(str(workdir), keep=2),
                          TrainerConfig(total_steps=12, ckpt_every=4,
                                        max_restarts=4),
                          executor=ex,
                          failure_injector=FailureInjector(fail_at))
        out = trainer.run()
        return out, ex

    out_f, ex_f = run(tmp_path / "fail", fail_at=(6,))
    out_c, ex_c = run(tmp_path / "clean", fail_at=())
    assert out_f["final_step"] == out_c["final_step"] == 12
    assert out_f["restarts"] == 1
    # failure *before* the first checkpoint: no state to restore, so the
    # books must reset rather than double-count the aborted attempt
    out_e, _ = run(tmp_path / "early", fail_at=(2,))
    assert out_e["dvfs"]["totals"]["steps"] == \
        out_c["dvfs"]["totals"]["steps"]
    ft, ct = out_f["dvfs"]["totals"], out_c["dvfs"]["totals"]
    # the restart rolled back to step 4's books and re-ran 4..11: exactly
    # one committed record per step, so both runs' books agree
    assert ft["steps"] == ct["steps"]
    assert ft["energy_j"] == pytest.approx(ct["energy_j"], rel=1e-9)
    assert ft["time_s"] == pytest.approx(ct["time_s"], rel=1e-9)
    assert ft["energy_pct"] < 0


def test_transfer_chip_mismatch_raises(setup):
    """Cross-chip transfer would silently map every pair to auto —
    refuse it up front, like the executors do."""
    cfg, shape, chip, src = setup
    with pytest.raises(ValueError, match="planned for"):
        transfer_train_plan(src, cfg, get_chip("rtx3080ti"), shape,
                            MeshSpec(dp=2), n_reps=1)


def test_transfer_meta_provenance(setup):
    cfg, shape, chip, src = setup
    xfer = transfer_train_plan(src, cfg, chip, shape,
                               MeshSpec(dp=2, tp=2), n_reps=2)
    assert xfer.meta["transferred"] is True
    assert xfer.meta["mesh"] == "dp2_tp2"
    assert xfer.meta["dp"] == 2 and xfer.meta["tp"] == 2
    for seg in xfer.segments:
        assert seg.schedule.meta["transferred_from"]["model"] == "gpt3-xl"


def test_transfer_rejects_a_serve_plan(setup):
    """The train transfer takes train plans only, as the serve transfer
    takes serve plans only."""
    cfg, shape, chip, src = setup
    serve = DvfsPlan(chip_name=src.chip_name, kind="serve",
                     segments=list(src.segments), meta=dict(src.meta))
    with pytest.raises(ValueError, match="not a train plan"):
        transfer_train_plan(serve, cfg, chip, shape, MeshSpec(dp=2),
                            n_reps=1)


def test_transferred_plan_roundtrips_and_executes(setup, tmp_path):
    """transfer_train_plan emits a train DvfsPlan with the train scopes
    in step order; it survives save/load and replays through the
    governor executor."""
    cfg, shape, chip, src = setup
    xfer = transfer_train_plan(src, cfg, chip, shape, MeshSpec(dp=2),
                               n_reps=2)
    assert xfer.kind == "train"
    assert [s.scope for s in xfer.segments] \
        == ["train-fwd", "train-bwd", "train-opt"]
    path = str(tmp_path / "xfer.json")
    xfer.save(path)
    back = DvfsPlan.load(path)
    assert back.summary() == xfer.summary()
    ex = TrainGovernorExecutor(StaticPlanGovernor(back), chip)
    for s in range(2):
        ex.on_step(s)
    ex.finish()
    assert ex.summary()["totals"]["steps"] == 2 * len(xfer.segments)
