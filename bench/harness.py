"""What every cell of the chip benchmark shares.

The harness is driven by data: a cell in ``BENCHMARK.json`` names a
configuration and a traffic mix, and each is a file found by its name
(``configs/<config>.json``, ``traffic/<traffic>.json``).  Per-layer metrics
are small readers in ``metrics/<metric>.py`` and kernel operation counts
live in ``kernels/<kernel>.py``; both are loaded by name here.  Nothing in
this file knows a particular cell.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import math
import os
import sys
import tempfile
from contextlib import contextmanager
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str):
    """(benchmark, cell, configuration, traffic) of one cell of
    ``BENCHMARK.json``: the configuration is the file its ``configs``
    entry names, the traffic ``traffic/<traffic>.json``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel(name: str):
    """``kernels/<name>.py``: the operations and bytes one call needs."""
    return load_module(BENCH / "kernels" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def device_check(chips: int):
    """The devices of this run; exits before any model is built unless
    JAX finds ``chips`` TPU chips or more."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{d.platform!r}); this benchmark runs on the chip")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` in the
    checkout.  Every program is cached, however quickly it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def import_program():
    """Put the program under test on the path (``src/`` of the checkout)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class Seeds:
    """Every random input of a run, derived from ``--seed`` alone.  Seeds
    of any size are accepted: each stream is drawn from a
    ``SeedSequence`` of the whole number."""

    def __init__(self, seed: int):
        weights, data = np.random.SeedSequence(int(seed)).spawn(2)
        self.weights = int(weights.generate_state(1)[0] & 0x7FFFFFFF)
        self.data = int(data.generate_state(1)[0])

    def weight_key(self):
        import jax
        return jax.random.PRNGKey(self.weights)


class CompileCounter:
    """Counts backend compilations from JAX's own monitoring events; the
    measured window must see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free_device_memory(*trees):
    """Delete the device arrays of ``trees`` now, so that what runs next
    (the reference) finds the memory free."""
    import jax
    for t in trees:
        for leaf in jax.tree.leaves(t):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
    gc.collect()


@contextmanager
def traced(enabled: bool):
    """Profile the enclosed block into a temporary directory (under
    ``TMPDIR``) and yield a holder whose ``path`` is the ``.xplane.pb``
    once the block has ended.  The window of the trace is the host span
    ``bench.window`` that the block opens."""
    import jax
    holder = type("Trace", (), {"path": None, "dir": None})()
    if not enabled:
        yield holder
        return
    holder.dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(holder.dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield holder
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(holder.dir, "**", "*.xplane.pb"),
                      recursive=True)
    holder.path = found[0] if found else None


def span(name: str):
    """A host span in the profiler's trace; free when no trace is on."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def remove_trace(holder):
    import shutil
    if holder.dir:
        shutil.rmtree(holder.dir, ignore_errors=True)


def per_layer(bench: dict, cell: dict, ctx) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that this cell reports:
    each is read by ``metrics/<name>.py``; a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_limits(readings: dict, limits: dict) -> tuple:
    """``correct`` and the numbers compared, each beside its limit."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok &= good
        # JSON has no infinity: a reading that is not a finite number is
        # printed as null, and fails
        checks[name] = {"value": value if good or (
            value is not None and math.isfinite(value)) else None,
            "limit": limit}
    return ok, checks


def emit(result: dict, checks: dict) -> None:
    """Print the numbers compared as the last lines of standard error and
    the result as the last line of standard output, ``checks`` last."""
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)


def kernel_roofline(ctx, name: str):
    """Share of its roofline, in %, that kernel ``name`` reached in the
    trace: for each chip, the least time the chip could take for what the
    calls need (the larger of operations over peak and bytes over peak
    bandwidth) over the kernel's device time; the mean over chips.  None
    when no call of the kernel ran."""
    k = kernel(name)
    flops, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    shares = []
    for dev in ctx.trace.devices:
        need = took = 0.0
        for op in ctx.trace.ops(dev):
            c = k.call_cost(op)
            if c is not None:
                need += max(c[0] / flops, c[1] / bw)
                took += op.duration * 1e-9
        if took:
            shares.append(need / took)
    return 100.0 * sum(shares) / len(shares) if shares else None
