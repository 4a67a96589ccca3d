"""Continuous batching vs wave batching: throughput, tail latency, energy,
paging, quantized KV, planner cost.

Five claims, measured:

1. **Scheduling** — on a skewed generation-length workload (a straggler in
   every wave), the continuous engine keeps every slot busy while the wave
   engine idles short requests behind the wave straggler.  Measured as
   real wall-clock tokens/sec and per-request completion "latency" (decode
   steps until a request finishes) on a CPU smoke model.  The engine's
   decode hot path is *sync-free*: batched bucketed prefill, on-device
   EOS/max-len termination, multi-chunk rounds with one host round-trip.
2. **Paging** — the same workload served by the paged-KV engine with
   **2x the slots at the same attention-KV HBM budget** (block-table page
   pool sized to the dense engine's byte count).
3. **Quantized KV** — an int8 (``--kv-dtype``) page pool doubles the page
   count of the bf16-paged pool and serves **2x the paged slot count at
   no more attention-KV HBM**, quantize-on-write + fused in-kernel
   dequant; measured peak pool occupancy backs the capacity claim.
4. **DVFS** — a :class:`~repro.dvfs.DvfsSession` plans every serving
   phase (prefill + per-bucket decode, for the full-size arch on the
   TPU-v5e-like chip) and the engine replays the resulting
   :class:`~repro.dvfs.DvfsPlan` through the session's governor
   executor, reporting executed energy vs the auto governor at <= the
   policy's time budget, with per-phase switch counts.  A second plan
   pass re-plans the decode phases on the *quantized* workload model
   (halved cache-read stream): the roofline feedback loop, recorded as
   per-bucket planned energy vs the bf16 plan at the same tau.
5. **Planner cost** — wall time of the (vectorized) per-phase planning
   itself, the number future PRs diff against.

Besides the usual artifact, the run writes a repo-root ``BENCH_serve.json``
(tokens/sec for the continuous *and* quantized engines, energy delta,
quantized-plan feedback record, planner wall time) as the perf trajectory
anchor; ``make bench-smoke`` re-runs the throughput section at toy scale
and fails on a >10% regression against that file, naming the offending
anchor and its delta.

Run:  PYTHONPATH=src python -m benchmarks.serve_continuous [--kv-dtype int8]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

ARCH = "llama3.2-1b"
SLOTS = 4
MAX_SEQ = 96
PAGE = 16
TAU = 0.005
N_REQUESTS = 16
KV_DTYPE = "int8"        # default --kv-dtype axis value
# decode shape for the roofline-feedback plan comparison: long contexts,
# the regime the doubled pool capacity exists to serve
FEEDBACK_DECODE_SEQ = 4096

BENCH_FILE = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_serve.json")


def _requests(vocab: int, n: int = N_REQUESTS):
    """Skewed mix: mostly short generations, a 6x straggler every 4th
    request (the wave scheduler's worst case)."""
    import jax  # noqa: F401  (repro.serve pulls jax; keep import local)
    from repro.serve import Request
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        plen = 8 if i % 2 == 0 else 12
        new = 48 if i % 4 == 1 else int(rng.integers(4, 10))
        reqs.append(Request(uid=i,
                            prompt=rng.integers(0, vocab, plen),
                            max_new_tokens=new))
    return reqs


def _drive(eng, vocab, n: int = N_REQUESTS, passes: int = 3) -> Dict:
    """Warm-up pass (compiles), then the best of ``passes`` timed
    steady-state passes (host scheduling noise dominates at this scale;
    steady-state throughput is the quantity under test)."""
    eng.generate(_requests(vocab, n))                 # warm-up
    best = None
    for _ in range(passes):
        eng.reset()
        reqs = _requests(vocab, n)
        t0 = time.perf_counter()
        eng.generate(reqs)
        dt = time.perf_counter() - t0
        if best is None or dt < best[0]:
            best = (dt, reqs, eng.n_decode_steps)
    dt, reqs, decode_steps = best
    tokens = sum(len(r.generated) for r in reqs)
    lat = np.array([r.finished_step for r in reqs], dtype=float)
    return {"wall_s": dt, "tokens": tokens,
            "tokens_per_s": tokens / dt,
            "decode_steps": decode_steps,
            "latency_steps_p50": float(np.percentile(lat, 50)),
            "latency_steps_p95": float(np.percentile(lat, 95))}


def _write_bench_file(payload: Dict) -> None:
    # merge-write (the serve_prefix benchmark idiom): other benchmarks
    # park their own anchors (prefix_*) in the same file, and a refresh
    # of this benchmark's anchors must not silently drop theirs
    merged: Dict = {}
    if os.path.exists(BENCH_FILE):
        with open(BENCH_FILE) as f:
            merged = json.load(f)
    merged.update(payload)
    with open(BENCH_FILE, "w") as f:
        json.dump(merged, f, indent=1, default=float)
        f.write("\n")


def _raw_chunk_rate(eng, calls: int = 8, windows: int = 2) -> float:
    """Raw jitted chunk-step throughput (steps/sec) on the engine's own
    state: the machine-speed calibration for the regression gate.  The
    engine's *efficiency* (tokens/sec divided by this) is noise-immune —
    host slowdowns hit both numerator and denominator.  Works for dense
    and paged engines (a paged chunk call takes the device block tables
    as an extra, non-donated operand)."""
    import jax
    st = eng.state
    fn = eng._chunk_fn(16)
    if eng.paged:
        st.sync_tables()

    def burst():
        nonlocal st
        args = (eng.params, st.cache, st.tokens, st.pos, st.remaining,
                eng.rng)
        out = fn(*args, st.tables_dev) if eng.paged else fn(*args)
        st.tokens, st.pos, st.cache, st.remaining, eng.rng = out[:5]
        return out[5]

    jax.block_until_ready(burst())                # warm
    best = 0.0
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(calls):
            last = burst()
        jax.block_until_ready(last)
        best = max(best, 16 * calls / (time.perf_counter() - t0))
    return best


_MODEL_CACHE: Dict = {}


def _smoke_model():
    """Build the benchmark's smoke model once per process."""
    if "m" not in _MODEL_CACHE:
        import jax
        from repro.configs import REGISTRY, smoke_config
        from repro.models import build_model
        cfg = dataclasses.replace(smoke_config(REGISTRY[ARCH]),
                                  compute_dtype="float32")
        model = build_model(cfg, block_k=16)
        params = model.init(jax.random.PRNGKey(0))
        _MODEL_CACHE["m"] = (model, params, cfg)
    return _MODEL_CACHE["m"]


def throughput_section(n_requests: int = N_REQUESTS,
                       include_wave: bool = True, passes: int = 3,
                       kv_dtype: str = KV_DTYPE) -> Dict:
    """Wave vs continuous vs paged-2x vs quantized-4x throughput on the
    skewed workload."""
    from repro.serve import ServeEngine, WaveEngine

    model, params, cfg = _smoke_model()

    out: Dict = {"arch": ARCH, "slots": SLOTS, "n_requests": n_requests}
    if include_wave:
        out["wave"] = _drive(WaveEngine(model, params, batch_slots=SLOTS,
                                        max_seq=MAX_SEQ), cfg.vocab_size,
                             n_requests)
    cont = ServeEngine(model, params, batch_slots=SLOTS, max_seq=MAX_SEQ)
    out["continuous"] = _drive(cont, cfg.vocab_size, n_requests,
                               passes=passes)
    # dense engines: kv_hbm_bytes is the attention-KV subset of the cache
    # (what paging would pool); cache_hbm_bytes adds dense SSM/conv state
    out["continuous"]["kv_hbm_bytes"] = cont.state.kv_hbm_bytes()
    out["continuous"]["cache_hbm_bytes"] = cont.state.cache_hbm_bytes()
    out["compile_stats"] = cont.compile_stats
    out["raw_chunk_steps_per_s"] = _raw_chunk_rate(cont)
    out["engine_efficiency"] = (out["continuous"]["tokens_per_s"]
                                / out["raw_chunk_steps_per_s"])
    if include_wave:
        out["throughput_speedup"] = (out["continuous"]["tokens_per_s"]
                                     / out["wave"]["tokens_per_s"])

    # paged engine: 2x the slots, page pool capped at the dense engine's
    # token capacity (SLOTS * MAX_SEQ) -> same attention-KV HBM budget
    paged = ServeEngine(model, params, batch_slots=2 * SLOTS,
                        max_seq=MAX_SEQ, paged=True, page_size=PAGE,
                        n_pages=SLOTS * MAX_SEQ // PAGE)
    out["paged_2x_slots"] = _drive(paged, cfg.vocab_size, n_requests)
    out["paged_2x_slots"]["kv_hbm_bytes"] = paged.state.kv_hbm_bytes()
    out["paged_2x_slots"]["slots"] = 2 * SLOTS
    out["paged_2x_slots"]["pool"] = paged.state.pool.stats()

    # quantized page pool: double the page count of the bf16-paged pool
    # (byte-identical at the bf16 serving dtype; the float32 smoke store
    # makes it ~0.5x here) and serve 2x the paged slot count again
    quant = ServeEngine(model, params, batch_slots=4 * SLOTS,
                        max_seq=MAX_SEQ, paged=True, page_size=PAGE,
                        n_pages=2 * SLOTS * MAX_SEQ // PAGE,
                        kv_dtype=kv_dtype)
    q = _drive(quant, cfg.vocab_size, n_requests, passes=passes)
    q["kv_dtype"] = kv_dtype
    q["slots"] = 4 * SLOTS
    q["kv_hbm_bytes"] = quant.state.kv_hbm_bytes()
    q["pool"] = quant.state.pool.stats()
    q["slot_ratio_vs_paged"] = (4 * SLOTS) / (2 * SLOTS)
    q["kv_hbm_ratio_vs_paged"] = (q["kv_hbm_bytes"]
                                  / out["paged_2x_slots"]["kv_hbm_bytes"])
    q["raw_chunk_steps_per_s"] = _raw_chunk_rate(quant)
    q["engine_efficiency"] = q["tokens_per_s"] / q["raw_chunk_steps_per_s"]
    out["quantized"] = q
    return out


def trace_overhead_section(passes: int = 12) -> Dict:
    """Tokens/sec with the obs tracer attached vs without, same smoke
    engine; the ratio gates tracing's hot-path cost (<= 1% target).
    The timed passes interleave the two engines in *alternating* order
    and each side keeps its best — host scheduling noise at this scale
    swings single runs +/-15%, far above the real cost of a few dict
    appends (~10us/event, <1% of a run), and alternating best-of-N
    pits both sides against the same noise floor."""
    from repro.obs import Tracer
    from repro.serve import ServeEngine

    model, params, cfg = _smoke_model()
    plain = ServeEngine(model, params, batch_slots=SLOTS, max_seq=MAX_SEQ)
    tracer = Tracer(meta={"bench": "trace_overhead"})
    traced = ServeEngine(model, params, batch_slots=SLOTS, max_seq=MAX_SEQ,
                         tracer=tracer)
    plain.generate(_requests(cfg.vocab_size))          # warm-up/compile
    traced.generate(_requests(cfg.vocab_size))
    times: Dict[str, List[float]] = {"plain": [], "traced": []}
    tokens = 0
    for i in range(passes):
        order = (("plain", plain), ("traced", traced))
        for name, eng in (order if i % 2 == 0 else order[::-1]):
            eng.reset()
            reqs = _requests(cfg.vocab_size)
            t0 = time.perf_counter()
            eng.generate(reqs)
            times[name].append(time.perf_counter() - t0)
            tokens = sum(len(r.generated) for r in reqs)
    p = tokens / min(times["plain"])
    t = tokens / min(times["traced"])
    return {"plain_tokens_per_s": p, "traced_tokens_per_s": t,
            "traced_events": len(tracer.events),
            "trace_overhead": t / p}


def planner_feedback_section(kv_dtype: str = KV_DTYPE,
                             n_reps: int = 10) -> Dict:
    """Re-plan the decode phases on the quantized workload model and
    compare against the bf16 plan at the same tau.

    KV quantization halves the decode cache-read stream, so the planner
    sees a higher-arithmetic-intensity decode roofline: planned base
    time/energy drop, the coalesced clock schedule re-groups, and the
    governed (planned) decode energy lands strictly below the bf16 plan's
    at every bucket — a strictly deeper serve energy cut at the same tau
    when both are measured against the shared un-governed bf16 baseline.
    """
    from repro.configs import REGISTRY
    from repro.configs.base import ShapeConfig
    from repro.core.objectives import WastePolicy
    from repro.core.power_model import get_chip
    from repro.dvfs import plan_serve_phases

    full = REGISTRY[ARCH]
    chip = get_chip("tpu-v5e")
    pre = ShapeConfig(name="serve_prefill", seq_len=512, global_batch=1,
                      kind="prefill")
    dec = ShapeConfig(name="serve_decode_kv", seq_len=FEEDBACK_DECODE_SEQ,
                      global_batch=2 * SLOTS, kind="decode")
    metas: Dict[str, Dict] = {}
    for kvd in (None, kv_dtype):
        plan = plan_serve_phases(
            full, chip, n_slots=2 * SLOTS, prefill_shape=pre,
            decode_shape=dec, policy=WastePolicy(TAU), n_reps=n_reps,
            kv_dtype=kvd)
        metas[kvd or "bf16"] = {
            s.name: s.schedule.meta for s in plan.segments
            if s.scope == "serve-decode"}

    buckets: Dict[str, Dict] = {}
    for ph in sorted(metas["bf16"], key=lambda s: int(s.split("@")[1])):
        m0, m1 = metas["bf16"][ph], metas[kv_dtype][ph]
        g0 = m0["base_energy_j"] * (1 + m0["energy_pct"] / 100)
        g1 = m1["base_energy_j"] * (1 + m1["energy_pct"] / 100)
        buckets[ph] = {
            "bf16_energy_pct": m0["energy_pct"],
            "quant_energy_pct": m1["energy_pct"],
            "bf16_energy_gov_j": g0, "quant_energy_gov_j": g1,
            # serve energy cut at the same tau, both against the shared
            # un-governed bf16 baseline (quantization + DVFS compound)
            "bf16_cut_vs_base": 1 - g0 / m0["base_energy_j"],
            "quant_cut_vs_base": 1 - g1 / m0["base_energy_j"],
        }
    top = max(buckets, key=lambda s: int(s.split("@")[1]))
    return {"kv_dtype": kv_dtype, "tau": TAU,
            "decode_seq_len": FEEDBACK_DECODE_SEQ, "n_slots": 2 * SLOTS,
            "buckets": buckets, "top_bucket": top,
            **{f"top_{k}": v for k, v in buckets[top].items()}}


def main(verbose: bool = True, kv_dtype: str = KV_DTYPE) -> Dict:
    from repro.configs import REGISTRY
    from repro.configs.base import ShapeConfig
    from repro.dvfs import DvfsSession
    from repro.serve import ServeEngine
    from .common import save_artifact

    # --- 1-3. scheduling + paging + quantized: wall-clock tokens/sec ----
    out = throughput_section(kv_dtype=kv_dtype)
    speedup = out["throughput_speedup"]

    # --- 4. DVFS: plan the full-size arch, replay through the engine ----
    # One DvfsSession runs campaign -> plan -> govern -> meter; the
    # kernel-static governor + simulated controller replay the planned
    # schedules as they are.
    full = REGISTRY[ARCH]
    pre = ShapeConfig(name="serve_prefill", seq_len=512, global_batch=1,
                      kind="prefill")
    dec = ShapeConfig(name="serve_decode", seq_len=512, global_batch=SLOTS,
                      kind="decode")
    from repro.obs import Tracer
    tracer = Tracer(meta={"bench": "serve_continuous", "arch": ARCH,
                          "chip": "tpu-v5e", "tau": TAU})
    sess = DvfsSession(chip="tpu-v5e", tau=TAU, n_reps=10, tracer=tracer)
    sess.plan_serve(full, n_slots=SLOTS, prefill_shape=pre,
                    decode_shape=dec)
    planner_wall_s = sess.planner_wall_s
    chip = sess.chip
    model, params, cfg = _smoke_model()
    eng = ServeEngine(model, params, batch_slots=SLOTS, max_seq=MAX_SEQ,
                      executor=sess.serve_executor(), tracer=tracer)
    eng.generate(_requests(cfg.vocab_size))
    energy = eng.energy_summary()
    sess.close()
    os.makedirs("artifacts", exist_ok=True)
    trace_path = tracer.save("artifacts/serve_continuous.trace.json")

    # --- 4a. tracing overhead on the hot path (gated in bench-smoke) ----
    overhead = trace_overhead_section()

    # --- 4b. roofline feedback: re-plan on the quantized workload -------
    feedback = planner_feedback_section(kv_dtype=kv_dtype)

    out.update({"tau": TAU, "energy": energy,
                "planner_wall_s": planner_wall_s,
                "quantized_plan": feedback,
                "trace_overhead": overhead,
                "trace_path": trace_path})
    save_artifact("serve_continuous", out)

    # --- 5. perf-trajectory anchor (repo root, diffed by future PRs) ----
    tot = energy["totals"]
    q = out["quantized"]
    _write_bench_file({
        "arch": ARCH, "slots": SLOTS, "n_requests": N_REQUESTS,
        "tokens_per_s": out["continuous"]["tokens_per_s"],
        "engine_efficiency": out["engine_efficiency"],
        "paged_2x_tokens_per_s": out["paged_2x_slots"]["tokens_per_s"],
        "throughput_speedup_vs_wave": speedup,
        "kv_dtype": kv_dtype,
        "quantized_tokens_per_s": q["tokens_per_s"],
        "quantized_engine_efficiency": q["engine_efficiency"],
        "quantized_slots": q["slots"],
        "quantized_slot_ratio_vs_paged": q["slot_ratio_vs_paged"],
        "quantized_kv_hbm_ratio_vs_paged": q["kv_hbm_ratio_vs_paged"],
        "quantized_peak_allocated_pages":
            q["pool"]["peak_allocated_pages"],
        "quantized_plan": feedback,
        "energy_pct": tot["energy_pct"], "time_pct": tot["time_pct"],
        "tau": TAU, "planner_wall_s": planner_wall_s,
        "trace_overhead": overhead["trace_overhead"],
    })

    if verbose:
        print(f"skewed workload, {N_REQUESTS} requests, {SLOTS} slots:")
        for tag in ("wave", "continuous", "paged_2x_slots", "quantized"):
            r = out[tag]
            print(f"  {tag:14s}: {r['tokens']} tok in {r['wall_s']:.2f}s"
                  f" ({r['tokens_per_s']:.1f} tok/s,"
                  f" {r['decode_steps']} decode steps,"
                  f" p50/p95 latency {r['latency_steps_p50']:.0f}/"
                  f"{r['latency_steps_p95']:.0f} steps)")
        print(f"  speedup    : {speedup:.2f}x tokens/sec (continuous/wave)")
        pp = out["paged_2x_slots"]["pool"]
        print(f"  paged      : {out['paged_2x_slots']['slots']} slots at "
              f"{out['paged_2x_slots']['kv_hbm_bytes']/1e3:.0f} kB paged "
              f"attention-KV vs dense "
              f"{out['continuous']['kv_hbm_bytes']/1e3:.0f} kB "
              f"attention-KV for {SLOTS} "
              f"(+{(out['continuous']['cache_hbm_bytes'] - out['continuous']['kv_hbm_bytes'])/1e3:.0f} kB "
              f"non-KV state); peak {pp['peak_allocated_pages']}"
              f"/{pp['n_pages']} pages")
        qp = q["pool"]
        print(f"  quantized  : {q['slots']} slots ({q['kv_dtype']}) at "
              f"{q['kv_hbm_bytes']/1e3:.0f} kB "
              f"({q['kv_hbm_ratio_vs_paged']:.2f}x paged bytes, "
              f"{q['slot_ratio_vs_paged']:.1f}x slots); peak "
              f"{qp['peak_allocated_pages']}/{qp['n_pages']} pages")
        print(f"  compile    : {out['compile_stats']}")
        print(f"  planner    : {planner_wall_s:.2f}s wall "
              f"(vectorized per-phase planning)")
        print(f"  tracing    : {overhead['trace_overhead']:.3f}x tokens/s "
              f"with tracer attached ({overhead['traced_events']} events); "
              f"trace -> {trace_path}")
        print(f"DVFS replay ({full.name} on {chip.name}, tau={TAU}):")
        for name, row in energy["phases"].items():
            if row["steps"]:
                print(f"  {name:10s} steps={row['steps']:3d} "
                      f"switches={row['n_switches']:3d} "
                      f"time {row['time_pct']:+7.4f}%  "
                      f"energy {row['energy_pct']:+8.3f}%")
        print(f"  total      time {tot['time_pct']:+7.4f}% "
              f"(budget {100*TAU:+.2f}%)  energy {tot['energy_pct']:+8.3f}%"
              f"  switches={tot['n_switches']}")
        print(f"quantized re-plan ({full.name}, decode "
              f"S={FEEDBACK_DECODE_SEQ}, {2*SLOTS} slots, same tau):")
        for ph, row in feedback["buckets"].items():
            print(f"  {ph:10s} planned energy "
                  f"{row['bf16_energy_gov_j']:.4f} J -> "
                  f"{row['quant_energy_gov_j']:.4f} J; cut vs bf16 base "
                  f"{100*row['bf16_cut_vs_base']:.2f}% -> "
                  f"{100*row['quant_cut_vs_base']:.2f}%")
    return out


def smoke(check: bool = True, tolerance: float = 0.10,
          confirm_retries: int = 2) -> int:
    """Toy-scale throughput run; non-zero exit on >tolerance regression
    against the checked-in ``BENCH_serve.json`` (``make bench-smoke``).

    Gates the continuous *and* the quantized engine.  Each variant passes
    if EITHER its absolute tokens/sec clears the floor OR its *normalized*
    engine efficiency does (tokens/sec over the same process's raw jitted
    chunk-step rate — a 2-core CI box swings its absolute wall clock
    +/-20% between processes, which the normalization cancels; a real
    hot-path regression lowers both measures).  A miss is re-confirmed
    with fresh best-of-5 attempts before failing; the failure output
    names the offending anchor(s) and prints the delta vs baseline."""
    kv_dtype = KV_DTYPE
    if os.path.exists(BENCH_FILE):
        with open(BENCH_FILE) as f:
            base = json.load(f)
        kv_dtype = base.get("kv_dtype", KV_DTYPE)
    out = throughput_section(include_wave=False, passes=5,
                             kv_dtype=kv_dtype)
    # variant -> {anchor_name: measured}; gate is per-variant OR over its
    # two anchors (absolute + normalized)
    vals: Dict[str, Dict[str, float]] = {
        "continuous": {
            "tokens_per_s": out["continuous"]["tokens_per_s"],
            "engine_efficiency": out["engine_efficiency"]},
        "quantized": {
            "quantized_tokens_per_s": out["quantized"]["tokens_per_s"],
            "quantized_engine_efficiency":
                out["quantized"]["engine_efficiency"]},
    }
    print(f"bench-smoke: continuous "
          f"{vals['continuous']['tokens_per_s']:.1f} tok/s "
          f"(efficiency {vals['continuous']['engine_efficiency']:.3f}), "
          f"quantized[{kv_dtype}] "
          f"{vals['quantized']['quantized_tokens_per_s']:.1f} tok/s "
          f"(efficiency "
          f"{vals['quantized']['quantized_engine_efficiency']:.3f})")
    if not check:
        return 0
    if not os.path.exists(BENCH_FILE):
        print(f"bench-smoke: no {os.path.basename(BENCH_FILE)} baseline; "
              f"run `python -m benchmarks.serve_continuous` first")
        return 1
    if "tokens_per_s" not in base or "engine_efficiency" not in base:
        print("bench-smoke: baseline lacks tokens_per_s/engine_efficiency;"
              " refresh it with `python -m benchmarks.serve_continuous`")
        return 1
    gated = ["continuous"]
    if "quantized_tokens_per_s" in base:
        gated.append("quantized")
    else:
        print("bench-smoke: baseline predates the quantized anchors; "
              "gating continuous only (refresh BENCH_serve.json to gate "
              "the quantized variant)")

    def failing(variant: str) -> List[Tuple[str, float, float]]:
        """Anchors of ``variant`` below floor; empty when it passes."""
        misses = [(name, val, base[name] * (1.0 - tolerance))
                  for name, val in vals[variant].items()
                  if val < base[name] * (1.0 - tolerance)]
        # OR-gate: one clearing anchor clears the variant
        return misses if len(misses) == len(vals[variant]) else []

    for attempt in range(confirm_retries):
        bad = [v for v in gated if failing(v)]
        if not bad:
            break
        print(f"bench-smoke: {', '.join(bad)} below floor; re-confirming "
              f"({attempt + 1}/{confirm_retries})")
        retry = throughput_section(include_wave=False, passes=5,
                                   kv_dtype=kv_dtype)
        rvals = {
            "continuous": {
                "tokens_per_s": retry["continuous"]["tokens_per_s"],
                "engine_efficiency": retry["engine_efficiency"]},
            "quantized": {
                "quantized_tokens_per_s":
                    retry["quantized"]["tokens_per_s"],
                "quantized_engine_efficiency":
                    retry["quantized"]["engine_efficiency"]},
        }
        for variant, row in rvals.items():
            for name, val in row.items():
                vals[variant][name] = max(vals[variant][name], val)

    ok = True
    for variant in gated:
        misses = failing(variant)
        if misses:
            ok = False
            for name, val, floor in misses:
                print(f"bench-smoke FAIL [{name}]: {val:.3f} < floor "
                      f"{floor:.3f} (baseline {base[name]:.3f}, "
                      f"{100 * (val / base[name] - 1):+.1f}%)")
        else:
            anchors = ", ".join(
                f"{name} {val:.3f} (floor {base[name] * (1 - tolerance):.3f})"
                for name, val in vals[variant].items())
            print(f"bench-smoke OK [{variant}]: {anchors}")

    # tracing overhead gate: the obs tracer must cost <= 1% tokens/sec
    # on the hot path (retry-confirm with extra attempts — the ratio is
    # a quotient of two noisy timings, and a genuine >1% cost keeps
    # missing while a noise dip clears on re-measurement)
    if "trace_overhead" in base:
        ratio = trace_overhead_section()["trace_overhead"]
        for attempt in range(confirm_retries + 2):
            if ratio >= 0.99:
                break
            print(f"bench-smoke: trace_overhead {ratio:.3f} below 0.99; "
                  f"re-confirming ({attempt + 1}/{confirm_retries + 2})")
            ratio = max(ratio,
                        trace_overhead_section()["trace_overhead"])
        if ratio < 0.99:
            ok = False
            print(f"bench-smoke FAIL [trace_overhead]: {ratio:.3f} < "
                  f"0.99 (tracing costs >1% tokens/sec)")
        else:
            print(f"bench-smoke OK [trace_overhead]: {ratio:.3f} "
                  f"(floor 0.990)")
    print(f"bench-smoke: {tolerance:.0%} tolerance -> "
          f"{'OK' if ok else 'REGRESSION'}")
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser(prog="benchmarks.serve_continuous")
    ap.add_argument("--smoke", action="store_true",
                    help="throughput-only toy run (skips DVFS planning)")
    ap.add_argument("--check", action="store_true",
                    help="with --smoke: fail on >10%% regression vs "
                         "BENCH_serve.json (names the offending anchor)")
    ap.add_argument("--kv-dtype", default=KV_DTYPE,
                    help="quantized page-pool dtype for the quantized "
                         "axis (default: %(default)s)")
    args = ap.parse_args()
    if args.smoke:
        raise SystemExit(smoke(check=args.check))
    main(kv_dtype=args.kv_dtype)
