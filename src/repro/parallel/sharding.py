"""Sharding rules: logical axes -> mesh PartitionSpecs (FSDP + TP + EP + SP).

Every parameter declares logical axis names at build time (see
``ParamBuilder``); this module maps them onto the production mesh:

* ``heads``/``kv``/``mlp``/``vocab``/``experts``/``inner`` -> ``model``
  (tensor/expert parallelism),
* ``embed`` -> the data axes (``("pod","data")``) — ZeRO-3/FSDP weight
  sharding; combined with scan-over-layers the per-layer all-gather stays
  inside the loop body,
* anything else -> replicated.

A dim is only sharded if its size divides the mesh-axis product (no GSPMD
padding surprises on odd vocab sizes); each mesh axis is used at most once
per array.  KV caches get dedicated rules: batch -> data axes, and the
*sequence* dim of decode caches shards over the model (and, for
single-sequence long-context, also the data) axes — context-parallel
decode, which is what makes the 500k cells fit HBM.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "inner": ("model",),
    "embed": ("fsdp",),          # resolved to the data axes below
}


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[n] for n in names]))


def spec_for_axes(axes: Tuple[Optional[str], ...],
                  shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Build a PartitionSpec for one array given logical axes + shape."""
    used = set()
    entries = []
    for dim, name in zip(shape, axes):
        assign = None
        if name is not None and name in LOGICAL_RULES:
            cand = LOGICAL_RULES[name]
            if cand == ("fsdp",):
                cand = data_axes(mesh)
            cand = tuple(a for a in cand if a in mesh.axis_names
                         and a not in used)
            if cand and dim % _axis_size(mesh, cand) == 0:
                assign = cand if len(cand) > 1 else cand[0]
                used.update(cand)
            elif len(cand) > 1:
                # try a suffix (e.g. just "data" when pod doesn't divide)
                for k in range(1, len(cand)):
                    sub = cand[k:]
                    if dim % _axis_size(mesh, sub) == 0:
                        assign = sub if len(sub) > 1 else sub[0]
                        used.update(sub)
                        break
        entries.append(assign)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_specs(model, mesh: Mesh):
    """PartitionSpec pytree for a model's parameters."""
    axes_tree = model.param_axes()
    abstract = model.abstract_params()

    def make(axes, sds):
        return spec_for_axes(tuple(axes), sds.shape, mesh)

    return jax.tree.map(make, axes_tree, abstract,
                        is_leaf=lambda x: isinstance(x, tuple))


def param_shardings(model, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(model, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

def batch_specs(specs: Dict[str, Any], mesh: Mesh) -> Dict[str, P]:
    """Input-batch PartitionSpecs: leading (global-batch) dim over the data
    axes, everything else replicated."""
    dp = data_axes(mesh)
    dp_size = _axis_size(mesh, dp)

    def spec(sds):
        if sds.shape and sds.shape[0] % dp_size == 0 and sds.shape[0] > 1:
            return P(dp if len(dp) > 1 else dp[0])
        return P()

    return {k: spec(v) for k, v in specs.items() if k != "cache"}


_SEQ_MIN = 1024  # dims >= this in a cache leaf are treated as sequence dims


def cache_specs(cache_tree, mesh: Mesh):
    """PartitionSpecs for decode caches.

    Layout conventions (all families): leading dim(s) = layer/group stack
    (unsharded, scanned over); one batch dim == global_batch; optionally a
    long sequence dim.  Rules: batch -> data axes when divisible; the
    sequence dim -> model axis (plus the data axes when the batch could not
    use them: context-parallel single-sequence decode).
    """
    dp = data_axes(mesh)
    dp_size = _axis_size(mesh, dp)
    model_size = mesh.shape["model"]

    def spec(sds):
        shape = sds.shape
        entries: list = [None] * len(shape)
        # find batch dim: first dim after the leading stack dims that
        # matches... we use convention: caches are (L[, sub], B, ...) — take
        # the dim index of the first dim that is followed by larger dims
        # and shard it over data if divisible.
        # Heuristic: batch dim = last dim before the largest (seq) dim, or
        # dim 1 for (L, B, ...) layouts.
        sizes = list(shape)
        # seq dim: the largest dim >= _SEQ_MIN (excluding dim 0)
        seq_dim = None
        for i in range(1, len(sizes)):
            if sizes[i] >= _SEQ_MIN and (seq_dim is None
                                         or sizes[i] > sizes[seq_dim]):
                seq_dim = i
        # batch dim: by convention index 1 for 4/5-dim (L,B,...) caches,
        # index 2 for (G, sub, B, ...) 6-dim local caches
        batch_dim = 2 if len(sizes) == 6 else 1
        batch_ok = sizes[batch_dim] % dp_size == 0 and sizes[batch_dim] > 1
        if batch_ok:
            entries[batch_dim] = dp if len(dp) > 1 else dp[0]
        if seq_dim is not None and seq_dim != batch_dim:
            axes = ("model",) if batch_ok else tuple(dp) + ("model",)
            total = _axis_size(mesh, axes)
            if sizes[seq_dim] % total == 0:
                entries[seq_dim] = axes if len(axes) > 1 else axes[0]
            elif sizes[seq_dim] % model_size == 0:
                entries[seq_dim] = "model"
        else:
            # no seq dim (SSM state): shard the heads/channel dim over model
            for i in range(len(sizes) - 1, batch_dim, -1):
                if sizes[i] % model_size == 0 and sizes[i] >= model_size:
                    entries[i] = "model"
                    break
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree.map(spec, cache_tree)


def input_shardings(model, shape_cfg, mesh: Mesh):
    """Attach NamedShardings to the model's input_specs for lowering."""
    specs = model.input_specs(shape_cfg)
    bspecs = batch_specs(specs, mesh)
    out = {}
    for k, sds in specs.items():
        if k == "cache":
            cspec = cache_specs(sds, mesh)
            out[k] = jax.tree.map(
                lambda s, sp: jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=NamedSharding(mesh, sp)), sds, cspec)
        else:
            out[k] = jax.ShapeDtypeStruct(
                sds.shape, sds.dtype,
                sharding=NamedSharding(mesh, bspecs[k]))
    return out


def state_shardings(model, mesh: Mesh):
    """Shardings for TrainState(params, opt{m,v,step}, rng)."""
    pspec = param_specs(model, mesh)
    ns = lambda s: NamedSharding(mesh, s)
    params = jax.tree.map(ns, pspec, is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    return {"params": params,
            "opt": {"m": params, "v": params, "step": rep},
            "rng": rep}
