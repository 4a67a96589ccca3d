"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Builds the engine, serves a synthetic request batch through an executed
DVFS plan, and reports the per-phase plans — all through the
``repro.dvfs`` facade: one :class:`~repro.dvfs.DvfsSession` runs the
campaign, plans every serving phase with the chosen governor, wires the
engine executor, and freezes the report.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_config, smoke_config
from ..configs.base import ShapeConfig
from ..dvfs import DvfsSession
from ..models import build_model
from ..obs import Tracer
from ..serve import Request, ServeEngine
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--chip", default="tpu-v5e")
    ap.add_argument("--governor", default="kernel-static",
                    help="repro.dvfs governor registry name")
    ap.add_argument("--tau", type=float, default=0.005)
    ap.add_argument("--trace-out", default=None,
                    help="record a Chrome/Perfetto-loadable telemetry "
                         "trace (repro.obs schema) of the run here")
    args = ap.parse_args()
    enable_compile_cache()
    tracer = Tracer(meta={"launcher": "serve", "arch": args.arch,
                          "chip": args.chip,
                          "governor": args.governor}) \
        if args.trace_out else None

    cfg = smoke_config(get_config(args.arch)) if args.smoke \
        else get_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve launcher targets decoder LMs; use the "
                         "ServeEngine API directly for enc-dec")

    # offline: plan every serving phase of the full-size arch
    full = get_config(args.arch)
    pre = ShapeConfig(name="serve_prefill", seq_len=512, global_batch=1,
                      kind="prefill")
    dec = ShapeConfig(name="serve_decode", seq_len=512,
                      global_batch=args.slots, kind="decode")
    with DvfsSession(chip=args.chip, tau=args.tau,
                     governor=args.governor, tracer=tracer) as sess:
        plan = sess.plan_serve(full, n_slots=args.slots,
                               prefill_shape=pre, decode_shape=dec)
        for name, row in plan.summary()["phases"].items():
            print(f"[serve] {name:10s} plan: {row['energy_pct']:+7.3f}% "
                  f"energy at {row['time_pct']:+6.3f}% time "
                  f"({row['n_switches']} switches)")

        # online: the engine replays the plan through the session executor
        model = build_model(cfg, block_k=64)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServeEngine(model, params, batch_slots=args.slots,
                             max_seq=128, executor=sess.serve_executor(),
                             tracer=tracer)
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(4, 16))),
                        max_new_tokens=args.max_new_tokens)
                for i in range(args.requests)]
        t0 = time.perf_counter()
        out = engine.generate(reqs)
        dt = time.perf_counter() - t0
        n_tok = sum(len(r.generated) for r in out)
        print(f"[serve] {len(out)} requests, {n_tok} tokens in {dt:.2f}s "
              f"({n_tok/dt:.1f} tok/s on this host)")
        tot = sess.report()["executed"][0]["totals"]
    if tracer is not None:
        print(f"[serve] telemetry trace ({len(tracer.events)} events) "
              f"-> {tracer.save(args.trace_out)}")
    print(f"[serve] executed ({args.governor}): "
          f"{tot['energy_pct']:+.3f}% energy at {tot['time_pct']:+.4f}% "
          f"time vs auto ({tot['n_switches']} switches)")


if __name__ == "__main__":
    main()
