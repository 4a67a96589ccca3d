"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Production entry point: builds the model from the registry, plans the
per-phase DVFS schedule through a :class:`~repro.dvfs.DvfsSession` with
the chosen governor, and drives the fault-tolerant trainer with the
session's executor actuating (and metering) the plan around every step.
``--smoke`` runs the reduced config, small enough for a CPU.  A full
config needs the accelerator memory its parameters and AdamW state take
(16 bytes per parameter): on one 16 GB TPU v5e that is about 0.5B
parameters, so gpt3-xl at full depth trains on a sharded mesh
(``chip_smoke.py --chips 4`` runs that path).
"""
from __future__ import annotations

import argparse
import json

from ..configs import get_config, get_shape, smoke_config, smoke_shape
from ..ckpt import CheckpointManager
from ..data import DataPipeline
from ..dvfs import DvfsSession
from ..models import build_model
from ..obs import Tracer
from ..train import OptimizerConfig, make_train_step
from ..train.loop import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--chip", default="tpu-v5e")
    ap.add_argument("--dvfs", choices=("off", "strict", "relaxed"),
                    default="strict")
    ap.add_argument("--governor", default="kernel-static",
                    help="repro.dvfs governor registry name "
                         "(kernel-static | pass-level | edp | online)")
    ap.add_argument("--controller", default=None,
                    help="frequency-controller backend "
                         "(simulated | rate-limited)")
    ap.add_argument("--tau", type=float, default=0.01)
    ap.add_argument("--plan-out", "--schedule-out", dest="plan_out",
                    default=None,
                    help="save the planned DvfsPlan JSON here")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--trace-out", default=None,
                    help="record a Chrome/Perfetto-loadable telemetry "
                         "trace (repro.obs schema) of the run here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    if args.smoke:
        cfg = smoke_config(cfg)
        shape = smoke_shape(shape)
    print(f"[train] {cfg.name} x {shape.name} "
          f"({cfg.param_count()[0]/1e6:.1f}M params)")

    # --- DVFS plan for this workload (campaign -> plan -> govern) ---
    session = None
    executor = None
    tracer = Tracer(meta={"launcher": "train", "arch": cfg.name,
                          "shape": shape.name, "chip": args.chip,
                          "governor": args.governor}) \
        if args.trace_out else None
    if args.dvfs != "off":
        tau = 0.0 if args.dvfs == "strict" else args.tau
        session = DvfsSession(chip=args.chip, tau=tau,
                              governor=args.governor,
                              controller=args.controller,
                              tracer=tracer)
        plan = session.plan_train(get_config(args.arch),
                                  shape=get_shape(args.shape))
        tot = plan.summary()["phases"]
        print(f"[train] DVFS plan ({args.dvfs}, {args.governor}): " +
              "  ".join(f"{ph}: {row['energy_pct']:+.2f}%e/"
                        f"{row['time_pct']:+.2f}%t"
                        for ph, row in tot.items()))
        if args.plan_out:
            plan.save(args.plan_out)
            print(f"[train] plan -> {args.plan_out}")
        executor = session.train_executor()

    model = build_model(cfg, block_k=64)
    step = make_train_step(
        model, OptimizerConfig(lr=args.lr, decay_steps=args.steps),
        accum_steps=args.accum, remat=True,
        compress=args.compress_grads)
    pipeline = DataPipeline(vocab_size=cfg.vocab_size,
                            batch_per_host=shape.global_batch,
                            seq_len=shape.seq_len)
    ckpt_dir = args.ckpt_dir or f"artifacts/train_{cfg.name}"
    trainer = Trainer(model, step, pipeline,
                      CheckpointManager(ckpt_dir, keep=3),
                      TrainerConfig(total_steps=args.steps,
                                    ckpt_every=args.ckpt_every),
                      executor=executor)
    try:
        out = trainer.run()
    finally:
        # always hand the chip back to the auto governor, even when the
        # run dies mid-step — a real driver must not stay pinned low
        if session is not None:
            session.close()
    if tracer is not None:
        print(f"[train] telemetry trace ({len(tracer.events)} events) "
              f"-> {tracer.save(args.trace_out)}")
    print(f"[train] done: {json.dumps(out, default=float)}")


if __name__ == "__main__":
    main()
