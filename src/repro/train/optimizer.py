"""Optimizers in pure JAX (no optax dependency): AdamW + SGD-momentum.

States are pytrees parallel to params; all state in fp32 regardless of
param dtype (mixed-precision-safe).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip_norm: float = 1.0
    warmup_steps: int = 100
    decay_steps: int = 10000
    min_lr_frac: float = 0.1


def lr_at(cfg: OptimizerConfig, step) -> jnp.ndarray:
    """Linear warmup + cosine decay schedule."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.decay_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1.0 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), \
        norm


def init_opt_state(params) -> Dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def adamw_update(params, grads, state, cfg: OptimizerConfig, frozen=None):
    """One AdamW step.  Returns (new_params, new_state, metrics).

    ``frozen``, a tree of bools beside ``params``, marks state that is not
    trained (a router's selection bias): it keeps its value and moments
    and is left out of the clipping norm."""
    if frozen is not None:
        grads = jax.tree.map(lambda g, f: jnp.zeros_like(g) if f else g,
                             grads, frozen)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip_norm)
    step = state["step"] + 1
    lr = lr_at(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g32
        v = b2 * v + (1 - b2) * jnp.square(g32)
        mhat = m / bc1
        vhat = v / bc2
        p32 = p.astype(jnp.float32)
        step_ = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p32
        return (p32 - lr * step_).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    flat_f = jax.tree.leaves(frozen) if frozen is not None \
        else [False] * len(flat_p)
    new_p, new_m, new_v = [], [], []
    for p, g, m, v, f in zip(flat_p, flat_g, flat_m, flat_v, flat_f):
        np_, nm, nv = (p, m, v) if f else upd(p, g, m, v)
        new_p.append(np_)
        new_m.append(nm)
        new_v.append(nv)
    new_params = jax.tree.unflatten(treedef, new_p)
    new_state = {"m": jax.tree.unflatten(treedef, new_m),
                 "v": jax.tree.unflatten(treedef, new_v),
                 "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}


def sgdm_update(params, grads, state, cfg: OptimizerConfig,
                momentum: float = 0.9):
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip_norm)
    step = state["step"] + 1
    lr = lr_at(cfg, step)

    def upd(p, g, m):
        m = momentum * m + g.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * m).astype(p.dtype), m

    new_p, new_m = {}, {}
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    ps, ms = [], []
    for p, g, m in zip(flat_p, flat_g, flat_m):
        a, b = upd(p, g, m)
        ps.append(a)
        ms.append(b)
    return (jax.tree.unflatten(treedef, ps),
            {"m": jax.tree.unflatten(treedef, ms), "v": state["v"],
             "step": step},
            {"grad_norm": gnorm, "lr": lr})


UPDATES = {"adamw": adamw_update, "sgdm": sgdm_update}
