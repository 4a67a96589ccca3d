"""The paper's headline training claim, executed end-to-end (§5–6, §7–8).

Three measurements on the GPT-3-xl train step (seq 1024, batch 40), all
through the ``repro.dvfs`` facade:

1. **Kernel-level vs pass-level vs auto** — two :class:`DvfsSession`\\ s
   sharing one measurement campaign, one with the ``kernel-static``
   governor and one with ``pass-level``, both at the same relaxed-waste
   budget (tau = 0.6%, the paper's operating point) and *executed* over
   ``N_STEPS`` optimizer steps: per-phase clock replay, switch overhead
   charged, energy integrated against the auto-governor twin.
   Paper: kernel-level recovers 14.6% of training energy at 0.6% slowdown
   where pass-level recovers ~2%.
2. **DP transfer** — the single-device plan replayed under DP=2/4
   meshes (per-device batch 20/10) vs replanning each mesh from scratch.
3. **TP transfer** — the same plan replayed under TP=2/4 meshes
   (sharded kernels, roofline-remapped transfer) vs per-mesh replanning.
   Paper §7–8: the discovered frequencies translate across parallelism.

The full run also writes a repo-root ``BENCH_train.json`` perf anchor
(kernel/pass energy + time deltas), mirroring ``BENCH_serve.json``;
``make bench-smoke`` re-runs section 1 (``--smoke --check``) and fails if
the executed kernel-level plan regresses against that anchor.

Run:  PYTHONPATH=src python -m benchmarks.train_dvfs
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict

from repro.configs import get_config, get_shape
from repro.core import Campaign, WastePolicy, build_workload, get_chip
from repro.dvfs import DvfsSession
from repro.launch.mesh import MeshSpec
from repro.parallel.plan_transfer import compare_transfer
from .common import save_artifact

ARCH = "gpt3-xl"
SHAPE = "paper_gpt3xl"
CHIP = "tpu-v5e"          # the µs-switch chip: per-kernel DVFS is realizable
TAU = 0.006               # paper's 0.6% slowdown operating point
N_STEPS = 10
N_REPS = 5
MESHES = (MeshSpec(dp=2), MeshSpec(dp=4), MeshSpec(tp=2), MeshSpec(tp=4))

BENCH_FILE = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_train.json")


def _executed(session: DvfsSession, cfg, shape, table,
              n_steps: int = N_STEPS) -> Dict:
    """Plan with the session's governor against the shared table, then
    execute n_steps through the session executor."""
    session.plan_train(cfg, shape=shape, table=table)
    ex = session.train_executor()
    for s in range(n_steps):
        ex.on_step(s)
    session.close()
    return ex.summary()


def headline_section(n_steps: int = N_STEPS,
                     include_pass: bool = True) -> Dict:
    """Kernel-level (vs pass-level), executed through DvfsSession."""
    cfg = get_config(ARCH)
    shape = get_shape(SHAPE)
    chip = get_chip(CHIP)

    # one campaign; both governors plan against the same table
    kernels = build_workload(cfg, shape, include_optimizer=True)
    table = Campaign(chip, seed=0, n_reps=N_REPS).run(kernels)
    kernel_sess = DvfsSession(chip=chip, tau=TAU, n_reps=N_REPS)
    kernel = _executed(kernel_sess, cfg, shape, table, n_steps)
    out = {"cfg": cfg, "shape": shape, "chip": chip, "table": table,
           "kernel_sess": kernel_sess, "kernel": kernel}
    if include_pass:
        pass_sess = DvfsSession(chip=chip, tau=TAU, n_reps=N_REPS,
                                governor="pass-level")
        out["pass"] = _executed(pass_sess, cfg, shape, table, n_steps)
    return out


def main(verbose: bool = True) -> Dict:
    h = headline_section()
    cfg, shape, chip = h["cfg"], h["shape"], h["chip"]
    kernel, passl = h["kernel"], h["pass"]
    policy = WastePolicy(TAU)
    kernel_plan = h["kernel_sess"].plan

    transfer = [r.to_dict() for r in
                compare_transfer(kernel_plan, cfg, chip, shape,
                                 list(MESHES), policy, n_reps=N_REPS)]
    max_vs_replan = max(abs(r["energy_vs_replan_pct"]) for r in transfer)

    out = {
        "arch": ARCH, "chip": CHIP, "tau": TAU, "n_steps": N_STEPS,
        "kernel_level": kernel["totals"],
        "kernel_phases": kernel["phases"],
        "pass_level": passl["totals"],
        "transfer": transfer,
        "max_transfer_vs_replan_pct": max_vs_replan,
        "kernel_beats_pass": kernel["totals"]["energy_pct"]
        < passl["totals"]["energy_pct"],
        "plan_summary": kernel_plan.summary(),
    }
    save_artifact("train_dvfs", out)

    # perf-trajectory anchor (repo root, mirrors BENCH_serve.json)
    kt, pt = kernel["totals"], passl["totals"]
    with open(BENCH_FILE, "w") as f:
        json.dump({
            "arch": ARCH, "chip": CHIP, "tau": TAU, "n_steps": N_STEPS,
            "energy_pct": kt["energy_pct"], "time_pct": kt["time_pct"],
            "pass_energy_pct": pt["energy_pct"],
            "max_transfer_vs_replan_pct": max_vs_replan,
        }, f, indent=1, default=float)
        f.write("\n")

    if verbose:
        print(f"[train_dvfs] {ARCH} on {CHIP}, tau={TAU}, "
              f"{N_STEPS} executed steps:")
        print(f"  auto        :   +0.00% time    +0.00% energy")
        print(f"  pass-level  : {pt['time_pct']:+8.2f}% time "
              f"{pt['energy_pct']:+8.2f}% energy "
              f"({pt['n_switches']} switches)")
        print(f"  kernel-level: {kt['time_pct']:+8.2f}% time "
              f"{kt['energy_pct']:+8.2f}% energy "
              f"({kt['n_switches']} switches; paper: -14.6% at +0.6%)")
        for name, row in kernel["phases"].items():
            print(f"    {name:4s}: time {row['time_pct']:+7.3f}%  "
                  f"energy {row['energy_pct']:+8.3f}%  "
                  f"switches/step {row['n_switches'] // N_STEPS}")
        print(f"  plan transfer (vs per-mesh replanning):")
        for r in transfer:
            print(f"    {r['mesh']:10s}: xfer {r['transfer_energy_pct']:+7.2f}% "
                  f"replan {r['replan_energy_pct']:+7.2f}% "
                  f"-> within {r['energy_vs_replan_pct']:+5.2f}% "
                  f"(remapped {r['n_remapped']}, repaired {r['n_repaired']})")
        print(f"  max |transfer - replan| = {max_vs_replan:.2f}% "
              f"(criterion: <= 2%)")
    return out


def smoke(check: bool = True, energy_tolerance_pp: float = 1.0) -> int:
    """Headline-only run (skips transfer); non-zero exit when the
    executed kernel-level plan regresses against ``BENCH_train.json``.

    Gates: energy_pct may not rise more than ``energy_tolerance_pp``
    percentage points above the anchor (deeper savings always pass), and
    executed time must stay within the tau budget (+ a small slack for
    phase-boundary switches, which the planner cannot see).
    """
    h = headline_section(n_steps=2, include_pass=False)
    kt = h["kernel"]["totals"]
    print(f"bench-smoke(train): kernel-level {kt['energy_pct']:+.2f}% "
          f"energy at {kt['time_pct']:+.3f}% time")
    if not check:
        return 0
    if not os.path.exists(BENCH_FILE):
        print(f"bench-smoke(train): no {os.path.basename(BENCH_FILE)} "
              f"baseline; run `python -m benchmarks.train_dvfs` first")
        return 1
    with open(BENCH_FILE) as f:
        base = json.load(f)
    ceil = base["energy_pct"] + energy_tolerance_pp
    budget = 100.0 * TAU + 0.1
    ok = kt["energy_pct"] <= ceil and kt["time_pct"] <= budget
    print(f"bench-smoke(train): energy {kt['energy_pct']:+.2f}% "
          f"(ceiling {ceil:+.2f}%), time {kt['time_pct']:+.3f}% "
          f"(budget {budget:+.3f}%) -> {'OK' if ok else 'REGRESSION'}")
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser(prog="benchmarks.train_dvfs")
    ap.add_argument("--smoke", action="store_true",
                    help="headline-only run (skips plan transfer)")
    ap.add_argument("--check", action="store_true",
                    help="with --smoke: fail on regression vs "
                         "BENCH_train.json")
    args = ap.parse_args()
    if args.smoke:
        raise SystemExit(smoke(check=args.check))
    main()
