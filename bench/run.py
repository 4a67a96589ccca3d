"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, loads its configuration and
traffic files by name, checks that JAX finds the TPU chips the cell asks
for (and exits non-zero without a result otherwise), makes the weights on
the device from the seed, warms the cell's own programs from the
persistent compilation cache, measures for ``--seconds`` and checks the
timed path's outputs against the float32 reference.  The last line of
standard output is one JSON object: the end-to-end metrics with
``--trace 0``, the per-layer metrics read from the profiler's trace with
``--trace 1``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402


def run_cell(name: str, seed: int, seconds: float, trace_on: bool,
             devices=None, config=None):
    """One run of cell ``name``: the result object that ``main`` prints,
    the numbers compared beside their limits, and the runner's own output
    (its readings and host counts).  ``devices`` and ``config`` replace
    the chip check and the configuration file (the tests drive a small
    configuration on the CPU)."""
    bench, cell, cfg, traffic = H.find_cell(name)
    config = config or cfg
    if devices is None:
        devices = H.device_check(cell["chips"])
    H.import_program()
    print(f"[bench] {name}: config {cell['config']}, traffic "
          f"{cell['traffic']}, seed {seed}, {seconds} s, trace "
          f"{int(trace_on)}; compile cache {H.enable_compile_cache()}")
    reference = H.load_module(H.BENCH / "reference"
                              / f"{config['reference']}.py")
    seeds = H.Seeds(seed)
    # the configuration's kind names its runner, bench/<kind>.py
    runner = importlib.import_module(f"bench.{config['kind']}")
    out = runner.run(
        cell, config, traffic, seeds, seconds, trace_on, devices, T_START,
        reference)
    correct, checks = H.check_limits(out["readings"],
                                     config["check"]["limits"])
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "device": device}
    if trace_on:
        from bench.trace import Trace
        tr = Trace.from_file(out["trace"].path)
        H.remove_trace(out["trace"])
        ctx = Context(cell, config, traffic, out["counts"], tr,
                      H.peaks(d.device_kind), len(devices))
        result["metrics"] = H.per_layer(bench, cell, ctx)
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.device_ops(),
                               "idle_gaps": tr.idle_gaps()}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in out["metrics"].items()
                             if k in units}
    return result, checks, out


class Context:
    """What a per-layer reader (``metrics/<name>.py``) reads: the cell,
    its configuration and traffic, the host's counts of the work done,
    the reduced trace, the device's peaks and the number of chips."""

    def __init__(self, cell, config, traffic, counts, trace, peaks, chips):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.counts, self.trace = counts, trace
        self.peaks, self.chips = peaks, chips


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, checks, _ = run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace))
    H.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
