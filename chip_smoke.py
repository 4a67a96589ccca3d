"""Run the serve and train paths once on a TPU, at full model width.

    python chip_smoke.py             # one chip: serve phase, train phase
    python chip_smoke.py --chips 4   # four chips: the sharded train step only

One chip:

* serve: llama3.2-1b at full size (random weights from a seed) behind
  ``ServeEngine(paged=True)`` with bf16 KV pages; 16 requests with prompts
  of 64-512 tokens and 32-128 new tokens.  The decode chunk must contain
  the compiled page-read kernel, and the greedy tokens of a few requests
  must agree with a plain float32 forward written out below.
* train: gpt3-xl at full width, cut in depth and batch to what one chip
  holds with AdamW state; a few steps through ``Trainer``.  The loss must
  be finite and fall.

Four chips (``--chips 4``): gpt3-xl at full depth, seq 1024, batch 40, on
a 2x2 (data, model) mesh with the FSDP+TP shardings.  The loss of the
first microbatch at the step-0 parameters, computed sharded, must agree
with the same loss computed on one device; then a few steps.

Times printed here are from one smoke pass, not benchmark numbers.  Any
failure raises, so the script exits non-zero; the last line of standard
output, printed only when every phase passed, is a JSON object with
``"ok"`` and the device as JAX reports it.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# A served greedy token counts as a mismatch only where the float32
# reference prefers another token by more than this many logits.  The
# served model computes in bfloat16 and its logits leave the unembedding
# in bfloat16: at this random init the top logits sit near 4, where one
# bfloat16 step is 1/32, and the 16-layer bfloat16 residual stream adds a
# few steps more.  0.25 is eight such steps.
MARGIN_BOUND = 0.25
# Sharded and one-device losses run the same bfloat16 program with sums
# split differently over the mesh; each token's logits move by a few
# bfloat16 steps, and the mean over 8192 tokens moves far less than this.
MESH_LOSS_TOL = 1e-2


def device_check(chips: int):
    """The devices JAX finds; exits before any model is built unless they
    are ``chips`` TPU chips or more."""
    import jax
    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); this script runs on the chip")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX found {len(devices)}")
    return devices


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def reference_logits(cfg, params, tokens):
    """Plain float32 forward of a llama-style decoder (RMSNorm, rotary
    positions, grouped-query attention, SwiGLU, tied unembedding) over one
    sequence: logits (S, vocab) at every position.  Written apart from the
    model code it checks."""
    import jax
    import jax.numpy as jnp
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    S = tokens.shape[0]
    pos = jnp.arange(S)
    inv = cfg.rope_theta ** (-jnp.arange(D // 2, dtype=jnp.float32)
                             / (D // 2))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(t):                                   # (S, heads, D)
        a, b = t[..., :D // 2], t[..., D // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def rms(t, w):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                 + 1e-5) * w

    causal = pos[:, None] >= pos[None, :]

    def layer(x, lp):
        a = lp["attn"]
        h = rms(x, lp["norm_attn"]["scale"])
        q = rope(jnp.einsum("sd,dhk->shk", h, a["wq"]))
        k = jnp.repeat(rope(jnp.einsum("sd,dhk->shk", h, a["wk"])),
                       H // KV, axis=1)
        v = jnp.repeat(jnp.einsum("sd,dhk->shk", h, a["wv"]), H // KV,
                       axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        x = x + jnp.einsum("qhk,hkd->qd", jnp.einsum("hqs,shk->qhk", p, v),
                           a["wo"])
        m = lp["mlp"]
        h = rms(x, lp["norm_mlp"]["scale"])
        return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) \
            @ m["w_down"], None

    wte = params["embed"]["wte"]
    x, _ = jax.lax.scan(layer, wte[tokens], params["layers"])
    return rms(x, params["final_norm"]["scale"]) @ wte.T


def serve_phase(cfg, *, slots=8, max_seq=1024, page_size=16, n_requests=16,
                prompt_lens=(64, 512), new_tokens=(32, 128), n_checked=4,
                seed=0):
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} of "
          f"{cfg.resolved_head_dim}, vocab {cfg.vocab_size}; random weights "
          f"(seed {seed}); paged bf16 KV, page {page_size}, {slots} slots, "
          f"max_seq {max_seq}", flush=True)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    specs = [(rng.integers(0, cfg.vocab_size,
                           int(rng.integers(prompt_lens[0],
                                            prompt_lens[1] + 1))),
              int(rng.integers(new_tokens[0], new_tokens[1] + 1)))
             for _ in range(n_requests)]

    def requests():
        return [Request(uid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(specs)]

    engine = ServeEngine(model, params, batch_slots=slots, max_seq=max_seq,
                         paged=True, page_size=page_size, kv_dtype="bf16")
    t0 = time.perf_counter()
    engine.generate(requests())               # warm-up: compiles every variant
    warm_s = time.perf_counter() - t0
    variants = engine.compile_stats
    engine.reset()
    t0 = time.perf_counter()
    out = engine.generate(requests())
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in out)
    if not all(r.done and len(r.generated) == r.max_new_tokens for r in out):
        raise RuntimeError("serve: a request did not finish its budget")
    if engine.compile_stats != variants:
        raise RuntimeError(f"serve: the timed pass compiled new variants "
                           f"{engine.compile_stats} after warm-up {variants}")
    print(f"[serve] smoke run, not a benchmark: {len(out)} requests, "
          f"{n_tok} generated tokens in {dt:.3f} s after a {warm_s:.1f} s "
          f"warm-up pass; jit variants {variants}", flush=True)

    # teacher-forced reference over prompt + generated tokens: the logits
    # at position P-1+i predict generated token i
    checked = sorted(out, key=lambda r: len(r.prompt))[::max(
        len(out) // n_checked, 1)][:n_checked]
    width = max(len(r.prompt) + len(r.generated) for r in checked)
    ref_fn = jax.jit(functools.partial(reference_logits, cfg))
    n_pos = n_agree = n_decisive = 0
    bad = []
    for r in checked:
        seq = np.concatenate([r.prompt, r.generated]).astype(np.int32)
        padded = np.zeros(width, np.int32)
        padded[:seq.size] = seq
        with jax.default_matmul_precision("highest"):
            logits = ref_fn(params, jnp.asarray(padded))
        P, N = len(r.prompt), len(r.generated)
        lg = np.asarray(logits[P - 1:P - 1 + N])
        if not np.all(np.isfinite(lg)):
            raise RuntimeError(f"serve: non-finite reference logits for "
                               f"request {r.uid}")
        top2 = np.sort(lg, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        want = lg.argmax(-1)
        got = np.asarray(r.generated)
        n_pos += N
        n_agree += int((want == got).sum())
        n_decisive += int((margin > MARGIN_BOUND).sum())
        for i in np.nonzero((want != got) & (margin > MARGIN_BOUND))[0]:
            bad.append((r.uid, int(i), int(got[i]), int(want[i]),
                        float(margin[i])))
    print(f"[serve] parity vs float32 reference: {len(checked)} requests, "
          f"{n_pos} tokens, {n_agree} equal to the reference argmax, "
          f"{n_decisive} with top-2 margin > {MARGIN_BOUND}, "
          f"{len(bad)} mismatches beyond the margin", flush=True)
    if bad:
        raise RuntimeError(f"serve: tokens differ from the reference "
                           f"(uid, index, served, reference, margin): "
                           f"{bad[:10]}")
    if n_decisive == 0:
        raise RuntimeError("serve: no token had a margin above the bound; "
                           "the parity check decided nothing")

    st = engine.state
    decode_hlo = engine._chunk_fn(1).lower(
        params, st.cache, st.tokens, st.pos, st.remaining, engine.rng,
        st.tables_dev).as_text()
    kernel_in_decode = "tpu_custom_call" in decode_hlo
    print(f"[serve] decode chunk HLO contains tpu_custom_call: "
          f"{kernel_in_decode}", flush=True)
    if not kernel_in_decode:
        raise RuntimeError("serve: the page-read kernel is not in the "
                           "decode chunk")


# ---------------------------------------------------------------------------
# train, one chip
# ---------------------------------------------------------------------------

def train_phase(cfg, *, n_layers=6, batch=8, seq_len=1024, steps=4,
                lr=1e-4, seed=0):
    """A few optimizer steps on one batch, served again at every step: the
    loss of a fixed batch falling shows the step at work, where the noise
    between batches of a random-init model could hide it."""
    from repro.ckpt import CheckpointManager
    from repro.data import DataPipeline
    from repro.models import build_model
    from repro.train import OptimizerConfig, make_train_step
    from repro.train.loop import Trainer, TrainerConfig

    class OneBatch(DataPipeline):
        def next_batch(self):
            self.state.step = 0
            return super().next_batch()

    full = cfg
    cfg = dataclasses.replace(full, n_layers=n_layers)
    print(f"[train] {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads "
          f"of {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; cuts: layers {full.n_layers} -> {n_layers}, "
          f"batch 40 -> {batch} at seq_len {seq_len} "
          f"({cfg.param_count()[0] / 1e9:.3f}B params, float32 params + "
          f"grads + AdamW moments {16 * cfg.param_count()[0] / 1e9:.1f} GB)",
          flush=True)
    model = build_model(cfg)
    step = make_train_step(
        model, OptimizerConfig(lr=lr, warmup_steps=1, decay_steps=steps),
        remat=True)
    pipeline = OneBatch(vocab_size=cfg.vocab_size, batch_per_host=batch,
                        seq_len=seq_len, seed=seed)
    ckpt_dir = ROOT / "artifacts" / "chip_smoke_train"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        trainer = Trainer(model, step, pipeline,
                          CheckpointManager(str(ckpt_dir), keep=1),
                          TrainerConfig(total_steps=steps,
                                        ckpt_every=steps),
                          seed=seed)
        trainer.run()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [h["loss"] for h in trainer.history]
    times = [h["wall_s"] for h in trainer.history]
    print(f"[train] smoke run, not a benchmark: losses on one batch at "
          f"lr {lr} "
          f"{[round(x, 4) for x in losses]}; step seconds "
          f"{[round(t, 3) for t in times]} (the first includes compile)",
          flush=True)
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train: loss did not fall {losses}")


# ---------------------------------------------------------------------------
# train, four chips
# ---------------------------------------------------------------------------

def mesh_phase(cfg, shape, *, devices, n_data=2, n_model=2, micro_per_dev=4,
               steps=3, seed=0):
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.data import DataPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.parallel.sharding import input_shardings, state_shardings
    from repro.train import (OptimizerConfig, TrainState, init_train_state,
                             make_train_step)

    B, S = shape.global_batch, shape.seq_len
    micro = micro_per_dev * n_data
    accum = B // micro
    if accum * micro != B:
        raise ValueError(f"batch {B} is not a multiple of {micro}")
    print(f"[mesh] {cfg.name}: {cfg.n_layers} layers, "
          f"{cfg.param_count()[0] / 1e9:.3f}B params, seq {S}, batch {B} "
          f"({accum} microbatches of {micro}) on a {n_data}x{n_model} "
          f"(data, model) mesh", flush=True)
    model = build_model(cfg)
    loss_fn = lambda p, b: model.loss(p, b, remat=False)[0]
    batch = DataPipeline(vocab_size=cfg.vocab_size, batch_per_host=B,
                         seq_len=S, seed=seed).next_batch()
    mb0 = {k: v[:micro] for k, v in batch.items()}

    # one-device reference first, freed before the sharded state exists
    key = jax.random.PRNGKey(seed)
    one = SingleDeviceSharding(devices[0])
    params0 = jax.jit(model.init, out_shardings=one)(jax.random.split(key)[0])
    ref = float(jax.jit(loss_fn)(params0, jax.device_put(mb0, one)))
    del params0

    mesh = make_host_mesh(n_data, n_model)
    sh = state_shardings(model, mesh)
    state_sh = TrainState(params=sh["params"], opt=sh["opt"], rng=sh["rng"])
    with jax.set_mesh(mesh):
        in_sh = {k: v.sharding for k, v in
                 input_shardings(model, shape, mesh).items()}
        state = jax.jit(functools.partial(init_train_state, model),
                        out_shardings=state_sh)(key)
        sharded = float(jax.jit(loss_fn)(state.params,
                                         jax.device_put(mb0, in_sh)))
        diff = abs(sharded - ref)
        print(f"[mesh] step-0 loss of the first microbatch: sharded "
              f"{sharded:.6f}, one device {ref:.6f}, |diff| {diff:.2e} "
              f"(tolerance {MESH_LOSS_TOL})", flush=True)
        if not diff <= MESH_LOSS_TOL:
            raise RuntimeError("mesh: sharded loss disagrees with the "
                               "one-device reference")
        step = jax.jit(
            make_train_step(model, OptimizerConfig(lr=1e-4, warmup_steps=1,
                                                   decay_steps=steps),
                            accum_steps=accum, remat=True),
            in_shardings=(state_sh, in_sh), out_shardings=(state_sh, None),
            donate_argnums=0)
        pipeline = DataPipeline(vocab_size=cfg.vocab_size, batch_per_host=B,
                                seq_len=S, seed=seed)
        losses, times = [], []
        for _ in range(steps):
            b = jax.device_put(pipeline.next_batch(), in_sh)
            t0 = time.perf_counter()
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
            times.append(time.perf_counter() - t0)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    print(f"[mesh] smoke run, not a benchmark: losses "
          f"{[round(x, 4) for x in losses]}; step seconds "
          f"{[round(t, 3) for t in times]} (the first includes compile); "
          f"peak bytes in use per device {peaks}", flush=True)
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"mesh: non-finite loss {losses}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip train phase")
    args = ap.parse_args()
    devices = device_check(args.chips)

    from repro.configs import get_config
    from repro.configs.shapes import PAPER_GPT3XL
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[cache] compile cache at {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(get_config("gpt3-xl"), PAPER_GPT3XL, devices=devices)
    else:
        serve_phase(get_config("llama3.2-1b"))
        train_phase(get_config("gpt3-xl"))
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    d = devices[0]
    print(json.dumps({"ok": True,
                      "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(devices)}}))


if __name__ == "__main__":
    main()
