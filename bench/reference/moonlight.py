"""Plain float32 Moonlight-16B-A3B (DeepSeek-V3 layout), written apart from
the program.

Pre-RMSNorm decoder, per layer ``x += MLA(RMSNorm(x))`` and ``x +=
FFN(RMSNorm(x))``; a final RMSNorm and an untied head.  No bias in any
projection.

* MLA: ``q = x W_q``, heads of ``nope + rope``; ``[c, k_r] = x W_kva``,
  ``c = RMSNorm(c)``; ``[k_nope, v] = c W_kvb`` per head; RoPE on
  ``q_rope`` and ``k_r`` in the interleaved layout (adjacent pairs rotate
  together, written evens first), ``k_r`` the same for every head; causal
  softmax attention at scale ``(nope + rope) ** -0.5``, then ``W_o``.
* FFN: a SwiGLU of width ``d_ff`` in the leading dense layers.  In the MoE
  layers: scores ``s = sigmoid(x W_r)`` over all experts, the top ``k`` of
  ``s + b`` chosen (``b`` the selection bias), weights ``s`` over their
  sum times ``routed_scale``; the output is the weighted sum over the chosen
  experts that this chip holds (experts ``0 .. held - 1``, each computed
  for every token and weighted by 0 where not chosen), plus a shared SwiGLU
  of ``n_shared_experts * d_expert``.
* Loss: mean token cross-entropy plus ``z_loss`` times the mean squared
  log-partition, plus ``aux_loss_weight`` times the sequence-wise balance
  loss of DeepSeek-V3 (eqs. 17-20) over all experts, the mean over the
  sequences and the MoE layers.
* Optimizer: AdamW with global-norm clipping, a linear warm-up and a
  cosine decay, all in float32, over every array but the selection bias.
  After each step ``b += bias_rate * sign(mean load - load)``, the load
  counting the step's assignments to each expert.

Weights come from the seed, as the program draws them: the ``i``-th
declared array is ``normal(fold_in(key, i)) * scale``, declared in the
order embeddings (token, head), final norm scale, then the dense layers and
after them the MoE layers, each as attention norm, ``wq``, ``w_kva``, the
latent's norm scale, ``w_kvb``, ``wo``, MLP norm, then ``w_gate``, ``w_up``,
``w_down`` (dense) or router, selection bias, the held experts' ``w_gate``,
``w_up``, ``w_down`` and the shared ``w_gate``, ``w_up``, ``w_down`` (MoE).
Norm scales are ones and the bias zeros; ``scale`` is 0.02 for the
embeddings and the head, ``(H * v_head_dim) ** -0.5`` for ``wo``,
``d ** -0.5`` for the experts' gate and up and ``d_expert ** -0.5`` for
their down, and fan-in ** -0.5 (the first axis) otherwise.  Nothing of the
program is imported.

Matrix products run at ``highest`` precision; ``quant`` rounds both
operands of every product to a lower storage type first (the control).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
EPS = 1e-5
Q_BLOCK = 1024          # queries per attention block (memory, not maths)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _shapes(m: dict):
    """(path, shape, scale) of every array in declaration order, layer
    arrays without their leading layer axis; scale None for ones, 0 for
    zeros."""
    d, H, V = m["d_model"], m["n_heads"], m["vocab_size"]
    a, e = m["mla"], m["moe"]
    r, nope, rope, dv = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                         a["qk_rope_head_dim"], a["v_head_dim"])
    f, n, E = e["d_expert"], e["experts_held"], e["n_experts"]
    fs = e["n_shared_experts"] * f
    out = [("embed/wte", (V, d), 0.02), ("embed/head", (d, V), 0.02),
           ("final_norm/scale", (d,), None)]
    attn = [("norm_attn/scale", (d,), None),
            ("attn/wq", (d, H, nope + rope), d ** -0.5),
            ("attn/w_kva", (d, r + rope), d ** -0.5),
            ("attn/kv_norm/scale", (r,), None),
            ("attn/w_kvb", (r, H, nope + dv), r ** -0.5),
            ("attn/wo", (H, dv, d), (H * dv) ** -0.5),
            ("norm_mlp/scale", (d,), None)]
    F = m["d_ff"]
    dense = attn + [("mlp/w_gate", (d, F), d ** -0.5),
                    ("mlp/w_up", (d, F), d ** -0.5),
                    ("mlp/w_down", (F, d), F ** -0.5)]
    moe = attn + [("moe/router", (d, E), d ** -0.5),
                  ("moe/select_bias", (E,), 0.0),
                  ("moe/w_gate", (n, d, f), d ** -0.5),
                  ("moe/w_up", (n, d, f), d ** -0.5),
                  ("moe/w_down", (n, f, d), f ** -0.5),
                  ("moe/shared/w_gate", (d, fs), d ** -0.5),
                  ("moe/shared/w_up", (d, fs), d ** -0.5),
                  ("moe/shared/w_down", (fs, d), fs ** -0.5)]
    for _ in range(m["n_dense_layers"]):
        out.extend(("dense_layers/" + p, s, c) for p, s, c in dense)
    for _ in range(m["n_layers"] - m["n_dense_layers"]):
        out.extend(("layers/" + p, s, c) for p, s, c in moe)
    return out


def _stacked(path: str) -> bool:
    return path.startswith(("layers/", "dense_layers/"))


def init(m: dict, key) -> Dict[str, jnp.ndarray]:
    """Flat ``{path: array}``; layer arrays stacked on a leading axis."""
    flat: Dict[str, list] = {}
    for i, (path, shape, scale) in enumerate(_shapes(m), start=1):
        if scale is None:
            a = jnp.ones(shape, jnp.float32)
        elif scale == 0.0:
            a = jnp.zeros(shape, jnp.float32)
        else:
            a = (jax.random.normal(jax.random.fold_in(key, i), shape)
                 * scale).astype(jnp.float32)
        flat.setdefault(path, []).append(a)
    return {p: (jnp.stack(v) if _stacked(p) else v[0])
            for p, v in flat.items()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mm(quant: Optional[str]):
    """einsum at highest precision, operands first rounded to ``quant``."""
    def mm(spec, a, b):
        if quant:
            dt = jnp.dtype(quant)
            a = a.astype(dt).astype(jnp.float32)
            b = b.astype(dt).astype(jnp.float32)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + EPS) * scale


def _rope(x, theta):
    """Interleaved RoPE over positions 0..S-1: x (B, S, h, D); the pair
    (x[2i], x[2i+1]) turns by position * theta ** (-2i / D); the result
    holds the turned evens, then the turned odds."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, D // 2, dtype=np.float64) * 2.0 / D)
    ang = jnp.asarray(np.arange(S)[:, None] * inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin], -1)


def _attention(mm, q, k, v):
    """Causal softmax attention, q/k (B, S, H, Dqk), v (B, S, H, Dv), taken
    a block of queries at a time so that the scores of one block are held
    at once."""
    B, S, H, D = q.shape
    qb = min(Q_BLOCK, S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = mm("bqhd,bshd->bhqs", qi, k) * D ** -0.5
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bhqs,bshd->bqhd", p, v)

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(S // qb))
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def _mla(mm, m, x, lp):
    a = m["mla"]
    r, nope = a["kv_lora_rank"], a["qk_nope_head_dim"]
    B, S, _ = x.shape
    H = m["n_heads"]
    q = mm("bsd,dhk->bshk", x, lp["attn/wq"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              m["rope_theta"])], -1)
    kva = mm("bsd,dr->bsr", x, lp["attn/w_kva"])
    c = _rms(kva[..., :r], lp["attn/kv_norm/scale"])
    kv = mm("bsr,rhk->bshk", c, lp["attn/w_kvb"])
    k_r = _rope(kva[:, :, None, r:], m["rope_theta"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (B, S, H, k_r.shape[-1]))],
        -1)
    o = _attention(mm, q, k, kv[..., nope:])
    return mm("bshk,hkd->bsd", o, lp["attn/wo"])


def _swiglu(mm, x, wg, wu, wd):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", x, wg))
              * mm("bsd,df->bsf", x, wu), wd)


def _moe(mm, m, x, lp, bias):
    """(output, balance loss, each expert's load) of an MoE layer."""
    e = m["moe"]
    E, K, n = e["n_experts"], e["top_k"], e["experts_held"]
    B, S, _ = x.shape
    s = jax.nn.sigmoid(mm("bsd,de->bse", x, lp["moe/router"]))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, K)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(-2)  # (B,S,E)
    w = s * chosen
    gate = w / w.sum(-1, keepdims=True) * e["routed_scale"]
    h = jax.nn.silu(mm("bsd,edf->bsef", x, lp["moe/w_gate"])) \
        * mm("bsd,edf->bsef", x, lp["moe/w_up"])
    y = mm("bsef,efd->bsed", h, lp["moe/w_down"])
    y = jnp.einsum("bsed,bse->bsd", y, gate[..., :n], precision=HIGHEST)
    y = y + _swiglu(mm, x, lp["moe/shared/w_gate"], lp["moe/shared/w_up"],
                    lp["moe/shared/w_down"])
    f = chosen.sum(1) * E / (K * S)                             # (B, E)
    pr = (s / s.sum(-1, keepdims=True)).mean(1)                 # (B, E)
    return y, jnp.mean(jnp.sum(f * pr, -1)), chosen.sum((0, 1))


def forward(m: dict, p: dict, bias, tokens, quant: Optional[str] = None):
    """Float32 logits (B, S, vocab), the balance loss (mean over the MoE
    layers) and the loads (MoE layers, experts) of ``tokens``."""
    mm = _mm(quant)
    x = p["embed/wte"][tokens]

    def attn_part(x, lp):
        return x + _mla(mm, m, _rms(x, lp["norm_attn/scale"]), lp)

    def dense(x, lp):
        x = attn_part(x, lp)
        h = _rms(x, lp["norm_mlp/scale"])
        return x + _swiglu(mm, h, lp["mlp/w_gate"], lp["mlp/w_up"],
                           lp["mlp/w_down"]), None

    def moe(x, lpb):
        lp, b = lpb
        x = attn_part(x, lp)
        y, bal, load = _moe(mm, m, _rms(x, lp["norm_mlp/scale"]), lp, b)
        return x + y, (bal, load)

    def layers(prefix):
        return {k[len(prefix):]: v for k, v in p.items()
                if k.startswith(prefix)}

    x, _ = jax.lax.scan(jax.checkpoint(dense), x, layers("dense_layers/"))
    x, (bal, load) = jax.lax.scan(jax.checkpoint(moe), x,
                                  (layers("layers/"), bias))
    x = _rms(x, p["final_norm/scale"])
    return mm("bsd,dv->bsv", x, p["embed/head"]), bal.mean(), load


def loss(m, p, bias, tokens, targets, quant=None):
    lg, bal, load = forward(m, p, bias, tokens, quant)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - gold + Z_LOSS * jnp.square(lse))
    return ce + m["moe"]["aux_loss_weight"] * bal, load


# ---------------------------------------------------------------------------
# training: three AdamW steps
# ---------------------------------------------------------------------------

def lr_at(o: dict, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["decay_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    frac = o["min_lr_frac"] + (1.0 - o["min_lr_frac"]) * 0.5 * (
        1.0 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * frac


def train_readings(m: dict, opt: dict, key, blocks, devices, rows: int,
                   quant: Optional[str] = None,
                   fault: Optional[str] = None) -> dict:
    """Losses of three AdamW steps from the seeded weights, the per-array
    norm of the first step's clipped gradient, and the per-array norm of
    the weights' change after the three steps (the selection bias, which is
    not trained, in neither).

    ``blocks`` are the ``(batch, seq + 1)`` token ids the program was fed:
    the first ``seq`` of a row are its inputs, the last ``seq`` its
    targets.  Gradients are summed over blocks of ``rows`` rows, one
    chip.  ``fault='half_batch'`` leaves half of every batch out (the mean
    over the rest).
    """
    if fault not in (None, "half_batch"):
        raise ValueError(f"moonlight reference: no fault {fault!r}")
    one = jax.sharding.SingleDeviceSharding(devices[0])
    params = jax.jit(lambda k: init(m, k), out_shardings=one)(
        jax.random.split(key)[0])
    bias = params.pop("layers/moe/select_bias")
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params)}
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    rate = m["moe"]["bias_rate"]

    @functools.partial(jax.jit, donate_argnums=(2,))
    def accumulate(p, bias, acc, t, y):
        (lv, load), g = jax.value_and_grad(
            lambda p: loss(m, p, bias, t, y, quant), has_aux=True)(p)
        return lv, load, jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, st, g, bias, load, step, n_blocks):
        g = {k: a / n_blocks for k, a in g.items()}
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in g.values()))
        g = {k: a * jnp.minimum(1.0, opt["grad_clip_norm"]
                                / jnp.maximum(gn, 1e-9))
             for k, a in g.items()}
        lr = lr_at(opt, step)
        mm = {k: b1 * st["m"][k] + (1 - b1) * g[k] for k in g}
        vv = {k: b2 * st["v"][k] + (1 - b2) * jnp.square(g[k]) for k in g}
        new = {k: p[k] - lr * ((mm[k] / (1 - b1 ** step))
                               / (jnp.sqrt(vv[k] / (1 - b2 ** step)) + eps)
                               + wd * p[k]) for k in p}
        bias = bias + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
        return new, {"m": mm, "v": vv}, bias, {
            k: jnp.linalg.norm(a) for k, a in g.items()}

    losses, grad_norms = [], None
    for step, blk in enumerate(blocks[:3], start=1):
        blk = np.asarray(blk)
        tok, tgt = blk[:, :-1], blk[:, 1:]
        if fault == "half_batch":
            tok, tgt = tok[:len(tok) // 2], tgt[:len(tgt) // 2]
        n_blocks = max(len(tok) // rows, 1)
        total, load = 0.0, 0.0
        g = jax.tree.map(jnp.zeros_like, params)
        with jax.default_matmul_precision("highest"):
            for i in range(n_blocks):
                sl = slice(i * rows, (i + 1) * rows)
                lv, lb, g = accumulate(params, bias, g,
                                       jax.device_put(tok[sl], one),
                                       jax.device_put(tgt[sl], one))
                total += float(lv)
                load = load + lb
            params, state, bias, gn = update(params, state, g, bias, load,
                                             float(step), float(n_blocks))
        losses.append(total / n_blocks)
        if step == 1:
            grad_norms = {k: float(v) for k, v in gn.items()}
    delta = jax.jit(lambda a, k: {
        n: jnp.linalg.norm(a[n] - b) for n, b in init(m, k).items()
        if n in a})(params, jax.random.split(key)[0])
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
