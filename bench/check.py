"""Readings for the limits that decide ``correct``, many seeds in one
process (not run by the benchmark's own runs).

    python3 bench/check.py --workload <cell> --what <what> --seeds 1 2 3 [--seconds s]

``--what`` (``program`` alone, or any of the others together):

* ``program``: the program's readings, by a whole run of the cell per
  seed (window of ``--seconds``);
* ``control``: the float32 reference put in the program's place but
  computed with every matrix product's operands rounded to float8
  (e4m3), the precision below the configuration's bfloat16, compared
  with the float32 reference as the program is;
* ``half_batch``: the reference leaving half of each batch out, the mean
  taken over the rest;
* ``no_reduce``: the reference leaving out the sum over the model axis
  (one shard's partial sums go on).

The last line of standard output is a JSON object of the readings by
seed and the largest of each.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402
from bench import run  # noqa: E402

CONTROL = "float8_e4m3fn"


def train_lower(config, traffic, seed, devices, reference, whats):
    """Readings of the reference in the program's place, for each of
    ``whats`` (``control`` or a fault), on the seed's token blocks."""
    from bench import train
    from bench import traffic as T
    seeds = H.Seeds(seed)
    blocks = [T.train_block(traffic, config["model"]["vocab_size"],
                            seeds.data, i) for i in range(train.CHECK_STEPS)]
    args = (config["model"], config["train"]["optimizer"],
            seeds.weight_key(), blocks, devices)
    rows = config["check"]["reference_rows"]
    ref = reference.train_readings(*args, rows=rows)
    out = {}
    for what in whats:
        low = reference.train_readings(
            *args, rows=rows, quant=CONTROL if what == "control" else None,
            fault=None if what == "control" else what)
        out.update({f"{what}.{k}": v
                    for k, v in train.compare(low, ref).items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", required=True, nargs="+",
                    choices=("program", "control", "half_batch",
                             "no_reduce"))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    _, cell, config, traffic = H.find_cell(args.workload)
    devices = H.device_check(cell["chips"])
    H.import_program()
    H.enable_compile_cache()
    reference = H.load_module(H.BENCH / "reference"
                              / f"{config['reference']}.py")
    by_seed = {}
    for seed in args.seeds:
        if args.what == ["program"]:
            result, checks, _ = run.run_cell(args.workload, seed,
                                             args.seconds, False,
                                             devices=devices)
            r = {k: c["value"] for k, c in checks.items()}
            r["correct"] = result["correct"]
        else:
            r = train_lower(config, traffic, seed, devices, reference,
                            args.what)
        by_seed[seed] = r
        print(f"[check] {args.workload} {args.what} seed {seed}: {r}")
    keys = [k for k in next(iter(by_seed.values())) if k != "correct"]
    print(json.dumps({"workload": args.workload, "what": args.what,
                      "by_seed": by_seed,
                      "max": {k: max(r[k] for r in by_seed.values())
                              for k in keys},
                      "min": {k: min(r[k] for r in by_seed.values())
                              for k in keys}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
