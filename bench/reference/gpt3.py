"""Plain float32 GPT-3 (arXiv:2005.14165), written apart from the program.

Pre-LayerNorm decoder: learned token and position embeddings, per layer
``x += attn(LN(x))`` and ``x += W2 gelu(W1 LN(x))`` with causal
multi-head attention and the tanh GELU, a final LayerNorm and an untied
output head.  No bias in the projections, as in the configuration.  The
training loss is the mean token cross-entropy plus ``z_loss`` times the
mean squared log-partition, and the optimizer is AdamW with global-norm
clipping, a linear warm-up and a cosine decay, all in float32.

Weights come from the seed, as the program draws them: the ``i``-th
declared array is ``normal(fold_in(key, i)) * scale``, declared in the
order embeddings (token, position, head), final norm (scale, bias), then
per layer attention norm, q, k, v, o, MLP norm, up, down; norm scales are
ones and biases zeros; ``scale`` is 0.02 for the embeddings and the head
and fan-in ** -0.5 otherwise (the first axis is the fan-in).  Nothing of
the program is imported.

Matrix products run at ``highest`` precision.  ``quant`` names a lower
storage type (``float8_e4m3fn``, ``int8``) to which both operands of every
product are rounded first: that is the control, the reference computed in
the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _shapes(m: dict):
    """(path, shape, scale) of every array in declaration order; scale
    None for ones, 0 for zeros."""
    d, H, D, F = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    out = [("embed/wte", (m["vocab_size"], d), 0.02),
           ("embed/wpe", (m["max_train_seq"], d), 0.02),
           ("embed/head", (d, m["vocab_size"]), 0.02),
           ("final_norm/scale", (d,), None),
           ("final_norm/bias", (d,), 0.0)]
    layer = [("norm_attn/scale", (d,), None), ("norm_attn/bias", (d,), 0.0),
             ("attn/wq", (d, H, D), d ** -0.5),
             ("attn/wk", (d, H, D), d ** -0.5),
             ("attn/wv", (d, H, D), d ** -0.5),
             ("attn/wo", (H, D, d), H ** -0.5),
             ("norm_mlp/scale", (d,), None), ("norm_mlp/bias", (d,), 0.0),
             ("mlp/w_up", (d, F), d ** -0.5),
             ("mlp/w_down", (F, d), F ** -0.5)]
    for _ in range(m["n_layers"]):
        out.extend(("layers/" + p, s, c) for p, s, c in layer)
    return out, len(layer)


def init(m: dict, key) -> Dict[str, jnp.ndarray]:
    """Flat ``{path: array}``; layer arrays stacked on a leading axis."""
    decl, per = _shapes(m)
    flat: Dict[str, list] = {}
    for i, (path, shape, scale) in enumerate(decl, start=1):
        k = jax.random.fold_in(key, i)
        if scale is None:
            a = jnp.ones(shape, jnp.float32)
        elif scale == 0.0:
            a = jnp.zeros(shape, jnp.float32)
        else:
            a = (jax.random.normal(k, shape) * scale).astype(jnp.float32)
        flat.setdefault(path, []).append(a)
    return {p: (jnp.stack(v) if p.startswith("layers/") else v[0])
            for p, v in flat.items()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mm(quant: Optional[str]):
    """einsum at highest precision, operands first rounded to ``quant``."""
    def mm(spec, a, b):
        if quant:
            dt = jnp.dtype(quant)
            if jnp.issubdtype(dt, jnp.integer):
                a, b = _int_round(a, dt), _int_round(b, dt)
            else:
                a = a.astype(dt).astype(jnp.float32)
                b = b.astype(dt).astype(jnp.float32)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _int_round(x, dt):
    """Symmetric per-tensor integer rounding (absmax scale)."""
    qmax = float(jnp.iinfo(dt).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / qmax
    return jnp.round(x / s) * s


def _ln(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                      * (x + 0.044715 * x ** 3)))


def _layer(mm, D, x, lp, fault: Optional[str]):
    S = x.shape[1]
    h = _ln(x, lp["norm_attn/scale"], lp["norm_attn/bias"])
    q = mm("bsd,dhk->bshk", h, lp["attn/wq"])
    k = mm("bsd,dhk->bshk", h, lp["attn/wk"])
    v = mm("bsd,dhk->bshk", h, lp["attn/wv"])
    s = mm("bqhk,bshk->bhqs", q, k) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bhqs,bshk->bqhk", p, v)
    wo, w_down = lp["attn/wo"], lp["mlp/w_down"]
    if fault == "no_reduce":
        # the row-parallel sum over the model axis left out: one shard's
        # partial sum (half the heads, half the MLP width) goes on
        H, F = wo.shape[0], w_down.shape[0]
        o, wo = o[:, :, :H // 2], wo[:H // 2]
    x = x + mm("bqhk,hkd->bqd", o, wo)
    h = _ln(x, lp["norm_mlp/scale"], lp["norm_mlp/bias"])
    u = _gelu(mm("bsd,df->bsf", h, lp["mlp/w_up"]))
    if fault == "no_reduce":
        u, w_down = u[..., :F // 2], w_down[:F // 2]
    return x + mm("bsf,fd->bsd", u, w_down)


def logits(m: dict, p: dict, tokens, quant: Optional[str] = None,
           fault: Optional[str] = None):
    """Float32 logits (B, S, vocab) at every position of ``tokens``."""
    mm = _mm(quant)
    S = tokens.shape[1]
    x = p["embed/wte"][tokens] + p["embed/wpe"][:S][None]
    layers = {k[len("layers/"):]: v for k, v in p.items()
              if k.startswith("layers/")}
    body = jax.checkpoint(
        lambda x, lp: (_layer(mm, m["head_dim"], x, lp, fault), None))
    x, _ = jax.lax.scan(body, x, layers)
    x = _ln(x, p["final_norm/scale"], p["final_norm/bias"])
    return mm("bsd,dv->bsv", x, p["embed/head"])


def loss(m, p, tokens, targets, quant=None, fault=None):
    lg = logits(m, p, tokens, quant, fault)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold + Z_LOSS * jnp.square(lse))


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


def _sharding(devices):
    """Each array split over all chips along its largest divisible axis
    (plain data parallelism over a one-axis mesh), or one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if len(devices) == 1:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return lambda shape: one, one
    mesh = Mesh(np.asarray(devices), ("d",))
    n = len(devices)

    def of(shape):
        axes = [None] * len(shape)
        dims = [i for i, s in enumerate(shape) if s % n == 0]
        if dims:
            axes[max(dims, key=lambda i: shape[i])] = "d"
        return NamedSharding(mesh, P(*axes))
    return of, NamedSharding(mesh, P("d"))


def train_readings(m: dict, opt: dict, key, blocks, devices, rows: int,
                   quant: Optional[str] = None,
                   fault: Optional[str] = None) -> dict:
    """Losses of three AdamW steps from the seeded weights, the per-leaf
    norm of the first step's clipped gradient, and the per-leaf norm of
    the weights' change after the three steps.

    ``blocks`` are the ``(batch, seq + 1)`` token ids the program was fed:
    the first ``seq`` of a row are its inputs, the last ``seq`` its
    targets.  Gradients are summed over blocks of ``rows`` rows.  ``fault='half_batch'``
    leaves half of every batch out (the mean over the rest);
    ``fault='no_reduce'`` leaves out the sum over the model axis.
    """
    of, rows_sharding = _sharding(devices)
    shapes = {p: s for p, s, _ in _shapes(m)[0]}
    shapes = {p: ((m["n_layers"],) + s if p.startswith("layers/") else s)
              for p, s in shapes.items()}
    p_sh = {p: of(s) for p, s in shapes.items()}
    make = jax.jit(lambda k: init(m, k), out_shardings=p_sh)
    params = make(jax.random.split(key)[0])
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params)}
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(m, p, t, y, quant, fault)))
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    add = jax.jit(lambda g, gb: jax.tree.map(jnp.add, g, gb),
                  donate_argnums=0)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, st, g, step, n_blocks):
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
        return new, {"m": mm, "v": vv}, {k: jnp.linalg.norm(a)
                                         for k, a in g.items()}

    losses, grad_norms = [], None
    for step, blk in enumerate(blocks[:3], start=1):
        blk = np.asarray(blk)
        tok, tgt = blk[:, :-1], blk[:, 1:]
        if fault == "half_batch":
            tok, tgt = tok[:len(tok) // 2], tgt[:len(tgt) // 2]
        n_blocks = max(len(tok) // rows, 1)
        total, g = 0.0, None
        with jax.default_matmul_precision("highest"):
            for i in range(n_blocks):
                sl = slice(i * rows, (i + 1) * rows)
                t = jax.device_put(tok[sl], rows_sharding)
                y = jax.device_put(tgt[sl], rows_sharding)
                lv, gb = grad_block(params, t, y)
                total += float(lv)
                g = gb if g is None else add(g, gb)
            params, state, gn = update(params, state, g, float(step),
                                       float(n_blocks))
        losses.append(total / n_blocks)
        if step == 1:
            grad_norms = {k: float(v) for k, v in gn.items()}
    # the weights' change against the seeded weights, drawn again rather
    # than held through the steps
    delta = jax.jit(lambda a, k: {
        n: jnp.linalg.norm(a[n] - b) for n, b in init(m, k).items()})(
            params, jax.random.split(key)[0])
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
