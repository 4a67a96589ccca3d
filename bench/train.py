"""Training cells: the jitted step of ``train/step.py`` with its state
donated, fed a new block of token ids from the traffic generator
(``bench/traffic.py``) at every step.

Set-up builds the step and its state from the seed, and drives it through
its first three steps with the window's own call and feed; those steps
compile the step and give the readings that decide ``correct``.  The
same step and state then run the window.  After the window the state is
freed and the float32 reference trains from the same seed on the same
three blocks, taking inputs and targets apart itself.
"""
from __future__ import annotations

import contextlib
import functools
import math
import statistics
import time


from bench import harness as H
from bench import traffic as T

CHECK_STEPS = 3
TRACE_SECONDS = 6.0


def build(config: dict, traffic: dict, seeds, devices):
    """The jitted step, the jitted state initializer, the feed and the mesh
    context for ``config``.  Returns (step, init, feed, context, model)."""
    import jax
    from repro.configs.base import ModelConfig, ShapeConfig
    from repro.models import build_model
    from repro.train import (OptimizerConfig, TrainState, init_train_state,
                             make_train_step)

    model = build_model(ModelConfig(**config["model"]))
    tr = config["train"]
    step_fn = make_train_step(model, OptimizerConfig(**tr["optimizer"]),
                              accum_steps=tr["accum_steps"],
                              remat=tr["remat"])
    B, S = traffic["global_batch"], traffic["seq_len"]
    init = functools.partial(init_train_state, model)
    if tr.get("mesh"):
        from repro.launch.mesh import make_host_mesh
        from repro.parallel.sharding import input_shardings, state_shardings
        mesh = make_host_mesh(*tr["mesh"])
        ctx = jax.set_mesh(mesh)
        sh = state_shardings(model, mesh)
        state_sh = TrainState(params=sh["params"], opt=sh["opt"],
                              rng=sh["rng"])
        with ctx:
            in_sh = {k: v.sharding for k, v in input_shardings(
                model, ShapeConfig("bench", S, B, "train"), mesh).items()}
        ctx = jax.set_mesh(mesh)
        init = jax.jit(init, out_shardings=state_sh)
        step = jax.jit(step_fn, in_shardings=(state_sh, in_sh),
                       out_shardings=(state_sh, None), donate_argnums=0)
    else:
        ctx = contextlib.nullcontext()
        in_sh = jax.sharding.SingleDeviceSharding(devices[0])
        init = jax.jit(init)
        step = jax.jit(step_fn, donate_argnums=0)

    n_fed = 0

    def feed(keep=None):
        """The next step's batch on the device; its block of token ids is
        appended to ``keep`` when given."""
        nonlocal n_fed
        blk = T.train_block(traffic, model.cfg.vocab_size, seeds.data, n_fed)
        n_fed += 1
        if keep is not None:
            keep.append(blk)
        return jax.device_put({"tokens": blk[:, :-1], "targets": blk[:, 1:]},
                              in_sh)

    return step, init, feed, ctx, model


def leaf_norms(tree, scale: float = 1.0):
    """``{path: norm}`` of every array of a parameter tree."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(k.key for k in path): jnp.linalg.norm(
        a.astype(jnp.float32)) * scale for path, a in flat}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``.

    * ``loss_gap``: the largest gap between the program's and the
      reference's loss over the checked steps.
    * ``grad_norm_gap``: by the worst array, the gap between the norms of
      the first step's clipped gradient, over the reference's norm of that
      array or of the median array, whichever is larger.
    * ``update_norm_gap``: the same for the weights' change after the
      checked steps, over the arrays whose reference gradient is at least
      a thousandth of the median array's (Adam moves the others by
      round-off alone).
    """
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    rg = ref["grad_norms"]
    med_g = statistics.median(rg.values())
    grad_gap = max(abs(prog["grad_norms"][k] - rg[k]) / max(rg[k], med_g)
                   for k in rg)
    counted = [k for k in rg if rg[k] >= 1e-3 * med_g]
    rd = ref["delta_norms"]
    med_d = statistics.median(rd[k] for k in counted)
    upd_gap = max(abs(prog["delta_norms"][k] - rd[k]) / max(rd[k], med_d)
                  for k in counted)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": upd_gap}


def model_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward operations per token the model needs: 6 per
    weight of the layers and the head (embedding lookups are not matrix
    products) plus causal attention; recomputation is not counted."""
    d, L = m["d_model"], m["n_layers"]
    attn_w = 4 * d * m["n_heads"] * m["head_dim"]
    mlp_w = 2 * d * m["d_ff"]
    n = L * (attn_w + mlp_w) + d * m["vocab_size"]
    # q.k and p.v over (seq + 1) / 2 keys on average, 2 operations a
    # product, times 3 for forward and backward
    attn = 3 * L * 2 * 2 * m["n_heads"] * m["head_dim"] * (seq + 1) / 2
    return 6 * n + attn


def run(cell: dict, config: dict, traffic: dict, seeds, seconds: float,
        trace_on: bool, devices, t_start: float, reference) -> dict:
    import jax

    counter = H.CompileCounter()
    step, init, feed, ctx, model = build(config, traffic, seeds, devices)
    b1 = config["train"]["optimizer"]["b1"]
    blocks = []
    with ctx:
        state = init(seeds.weight_key())
        losses = []
        for i in range(CHECK_STEPS):
            state, met = step(state, feed(blocks))
            losses.append(met["loss"])
            if i == 0:
                # Adam's first moment after one step is (1 - b1) times the
                # clipped gradient the optimizer got
                grad_norms = jax.device_get(
                    leaf_norms(state.opt["m"], 1.0 / (1.0 - b1)))
        delta = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, model.init(jax.random.split(k)[0]))))(
                state.params, seeds.weight_key())
        prog = {"losses": [float(x) for x in losses],
                "grad_norms": {k: float(v) for k, v in grad_norms.items()},
                "delta_norms": {k: float(v) for k, v in
                                jax.device_get(delta).items()}}
        setup_s = time.perf_counter() - t_start
        n_compiles = counter.n

        tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
        window_losses = []

        def steps_until(deadline):
            """Whole steps, one in flight ahead of the host, until the
            deadline; returns when the last has finished."""
            nonlocal state
            n, prev = 0, None
            while True:
                with H.span("bench.batch"):
                    b = feed()
                with H.span("bench.train_step"):
                    state, met = step(state, b)
                if prev is not None:
                    with H.span("bench.host_sync"):
                        window_losses.append(float(prev["loss"]))
                    n += 1
                prev = met
                if time.perf_counter() >= deadline:
                    break
            with H.span("bench.host_sync"):
                window_losses.append(float(prev["loss"]))
            return n + 1

        t0 = time.perf_counter()
        traced_steps = 0
        with H.traced(trace_on) as tr:
            if trace_on:
                traced_steps = steps_until(t0 + TRACE_SECONDS)
        t1 = time.perf_counter()
        n_steps = steps_until(t0 + seconds)
        t_end = time.perf_counter()
    compiles_in_window = counter.n - n_compiles
    peak = H.memory_peak(devices)
    H.free_device_memory(state)

    t_ref = time.perf_counter()
    with H.span("bench.reference"):
        ref = reference.train_readings(
            config["model"], config["train"]["optimizer"],
            seeds.weight_key(), blocks, devices,
            rows=config["check"]["reference_rows"])
    t_ref = time.perf_counter() - t_ref
    readings = compare(prog, ref)
    readings["compiles_in_window"] = float(compiles_in_window)
    attempted = traced_steps + n_steps
    failed = sum(1 for x in window_losses if not math.isfinite(x))

    print(f"[train] checked losses program {prog['losses']} reference "
          f"{ref['losses']}; window {n_steps} steps in {t_end - t1:.3f} s "
          f"(+{traced_steps} traced); set-up {setup_s:.3f} s; "
          f"compiles in window {compiles_in_window}; peak {peak} B; "
          f"reference {t_ref:.3f} s")
    counts = {"steps": n_steps, "seconds": t_end - t1,
              "tokens_per_step": tokens_per_step,
              "traced_steps": traced_steps,
              "model_flops_per_token": model_flops_per_token(
                  config["model"], traffic["seq_len"])}
    if trace_on:
        return {"readings": readings, "attempted": attempted,
                "failed": failed, "peak": peak, "trace": tr,
                "counts": counts, "window": (t0, t1)}
    rate = n_steps * tokens_per_step / (t_end - t0)
    return {"readings": readings, "attempted": attempted, "failed": failed,
            "peak": peak, "counts": counts,
            "metrics": {"train_tokens_per_s": rate, "setup_s": setup_s}}
