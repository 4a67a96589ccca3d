"""Serving example: continuous batching + executed phase-aware DVFS +
online re-planning, all through the repro.dvfs facade.

Decode workloads are HBM-bound (weight + KV-cache streaming), so the
waste planner finds much deeper core-clock reductions than in training —
the paper's §11 inference outlook, made concrete.  One
:class:`~repro.dvfs.DvfsSession` plans every serving phase (prefill plan
+ decode plans keyed by active-slot bucket) and the engine *executes*
the plan through the session's governor executor at every phase
transition.

The second half shows the :class:`~repro.dvfs.OnlineGovernor`: the same
plan under a drifted traffic mix strands time budget; the governor
detects the bucket-mix drift from runtime feedback, re-plans the decode
segments jointly over the observed mix (off the hot path), and recovers
the stranded energy.

Run:  PYTHONPATH=src python examples/serve_dvfs.py
"""
import dataclasses

import jax
import numpy as np

from repro.configs import REGISTRY, smoke_config
from repro.configs.base import ShapeConfig
from repro.core import (Campaign, WastePolicy, WorkloadBuilder,
                        decode_slot_buckets)
from repro.dvfs import (DvfsSession, OnlineGovernor, ServeGovernorExecutor,
                        StaticPlanGovernor, plan_decode_joint)
from repro.models import build_model
from repro.serve import Request, ServeEngine

SLOTS = 4
TAU = 0.005


def main():
    # --- offline: one session plans every serving phase -----------------
    full = REGISTRY["llama3.2-1b"]
    prefill = ShapeConfig(name="serve_prefill", seq_len=512,
                          global_batch=1, kind="prefill")
    decode = ShapeConfig(name="serve_decode", seq_len=512,
                         global_batch=SLOTS, kind="decode")
    sess = DvfsSession(chip="tpu-v5e", tau=TAU, n_reps=10)
    plan = sess.plan_serve(full, n_slots=SLOTS, prefill_shape=prefill,
                           decode_shape=decode)
    plan.save("artifacts/serve_phase_bundle.json")
    print("planned phases (governor=kernel-static):")
    for name, row in plan.summary()["phases"].items():
        print(f"  {name:10s} time {row['time_pct']:+7.3f}%  "
              f"energy {row['energy_pct']:+8.3f}%  "
              f"switches/step {row['n_switches']}")

    # --- online: continuous-batching engine executes the plan -----------
    cfg = dataclasses.replace(smoke_config(full), compute_dtype="float32")
    model = build_model(cfg, block_k=16)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, batch_slots=SLOTS, max_seq=96,
                         executor=sess.serve_executor())

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               rng.integers(4, 12)),
                    max_new_tokens=int(rng.integers(4, 24)))
            for i in range(10)]
    engine.generate(reqs)
    for r in reqs[:3]:
        print(f"req {r.uid}: prompt[{len(r.prompt)}] -> "
              f"{len(r.generated)} tokens, done at step {r.finished_step}")

    tot = engine.energy_summary()["totals"]
    print(f"executed: {tot['steps']} phase steps, "
          f"{tot['n_switches']} clock switches, "
          f"time {tot['time_pct']:+.4f}% vs auto, "
          f"energy {tot['energy_pct']:+.3f}% vs auto")
    sess.close()

    # --- the online governor on a drifted traffic mix -------------------
    chip = sess.chip
    policy = WastePolicy(0.01)
    camp = Campaign(chip, seed=0, n_reps=5)
    tables = {b: camp.run(WorkloadBuilder(full, decode,
                                          batch_override=b).build())
              for b in decode_slot_buckets(SLOTS)}
    planned_mix = {1: 0.30, 2: 0.30, 4: 0.40}
    drift = [1] * 2 + [2] * 13 + [4]       # observed mix ~ {.12,.81,.06}

    def serve_plan(mix):
        from repro.dvfs import DvfsPlan, compile_phase
        segs = plan_decode_joint(tables, mix, chip, policy)
        pre = compile_phase(tables[1], "prefill", chip, policy,
                            scope="serve-prefill")
        return DvfsPlan(chip_name=chip.name, kind="serve",
                        segments=[pre] + segs,
                        meta={"decode_mix": dict(mix)})

    gov = OnlineGovernor(serve_plan(planned_mix), policy=policy,
                         chip=chip, tables=tables, window=32)
    online = ServeGovernorExecutor(gov, chip)
    stale = ServeGovernorExecutor(
        StaticPlanGovernor(serve_plan(planned_mix)), chip)
    for i in range(320):
        online.on_decode(drift[i % len(drift)])
        stale.on_decode(drift[i % len(drift)])
    online.finish(), stale.finish()
    ev = gov.events[-1]
    print(f"\nonline governor: re-planned at revision {gov.revision} "
          f"({ev['reason']})")
    on, st = online.summary()["totals"], stale.summary()["totals"]
    print(f"  stale plan : time {st['time_pct']:+.4f}%  "
          f"energy {st['energy_pct']:+.4f}%")
    print(f"  online     : time {on['time_pct']:+.4f}%  "
          f"energy {on['energy_pct']:+.4f}%  "
          f"(recovered {st['energy_j'] - on['energy_j']:.3f} J of "
          f"stranded budget on the drifted mix)")


if __name__ == "__main__":
    main()
