"""Explicit shard_map collectives: the MoE token exchange as a true
all-to-all.

Under GSPMD auto-partitioning, the scatter/gather MoE dispatch lowers (on
some backends) to partial-gather + all-reduce of the full (T, d) token
tensor — ~4x the minimal wire traffic (EXPERIMENTS.md §Perf C-3).  This
module implements the exchange the hardware actually wants:

  1. each expert-parallel shard buckets its local tokens by destination
     shard (the shard owning the routed expert), into fixed-capacity send
     buffers (shard-local scatter — no collective),
  2. one ``lax.all_to_all`` moves the (ep, C, d) buffers,
  3. expert MLPs run on received tokens,
  4. the reverse ``all_to_all`` returns results; a shard-local gather
     restores token order.

Static shapes require a per-(src, dst) capacity; overflow tokens drop
(training semantics) — size ``capacity`` with the same factor as the
dense dispatch.  Wire bytes: 2 * T * d * dtype — the all-to-all minimum.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _bucket_by_dest(xt, flat_e, flat_w, ep: int, experts_per_shard: int,
                    capacity: int):
    """Shard-local: route (T_l*K) assignments into (ep, C) slots.

    Returns send buffers: x_send (ep, C, d), meta (ep, C, 3) holding
    (local_assignment_idx+1, local_expert_on_dest, valid)."""
    TK = flat_e.shape[0]
    d = xt.shape[-1]
    dest = flat_e // experts_per_shard                   # (TK,)
    # rank of each assignment within its destination bucket
    oh = jax.nn.one_hot(dest, ep, dtype=jnp.int32)       # (TK, ep)
    pos_all = jnp.cumsum(oh, axis=0) - oh
    pos = jnp.take_along_axis(pos_all, dest[:, None], axis=1)[:, 0]
    keep = pos < capacity
    oob = jnp.where(keep, pos, capacity)                 # drop -> OOB
    src_tok = jnp.arange(TK)                             # assignment index
    x_send = jnp.zeros((ep, capacity, d), xt.dtype)
    # xt is pre-expanded to one row per assignment (TK rows)
    x_send = x_send.at[dest, oob].set(
        jnp.where(keep[:, None], xt, 0), mode="drop")
    meta = jnp.zeros((ep, capacity, 2), jnp.int32)
    meta = meta.at[dest, oob, 0].set(src_tok + 1, mode="drop")
    meta = meta.at[dest, oob, 1].set(flat_e % experts_per_shard,
                                     mode="drop")
    return x_send, meta


def moe_all_to_all(xt, top_e, top_w, expert_fn: Callable, *,
                   n_experts: int, axis_name: str,
                   capacity_factor: float = 2.0):
    """Run ``expert_fn`` over tokens via an explicit all-to-all exchange.

    Must be called inside ``shard_map`` with the token dim sharded over
    ``axis_name`` and the experts owned shard-major.  xt: (T_l, d) local
    tokens; top_e/top_w: (T_l, K) routing.  expert_fn(local_expert_idx,
    x) -> y applies the shard's experts ((n_recv, d) + ids -> (n_recv,
    d)).  Returns (T_l, d) combined outputs.
    """
    ep = lax.axis_size(axis_name)
    experts_per_shard = n_experts // ep
    T_l, K = top_e.shape
    d = xt.shape[-1]
    TK = T_l * K
    capacity = max(int(capacity_factor * TK / ep), 1)

    x_rep = jnp.repeat(xt, K, axis=0)                    # (TK, d)
    flat_e = top_e.reshape(TK)
    flat_w = top_w.reshape(TK)
    x_send, meta = _bucket_by_dest(x_rep, flat_e, flat_w, ep,
                                   experts_per_shard, capacity)

    # the exchange: (ep, C, d) -> (ep, C, d) with src/dst transposed
    x_recv = lax.all_to_all(x_send, axis_name, split_axis=0,
                            concat_axis=0, tiled=True)
    meta_recv = lax.all_to_all(meta, axis_name, split_axis=0,
                               concat_axis=0, tiled=True)

    flat_x = x_recv.reshape(ep * capacity, d)
    local_eid = meta_recv[..., 1].reshape(ep * capacity)
    valid = meta_recv[..., 0].reshape(ep * capacity) > 0
    y = expert_fn(local_eid, flat_x)
    y = jnp.where(valid[:, None], y, 0).astype(xt.dtype)

    # reverse exchange + shard-local combine
    y_send = y.reshape(ep, capacity, d)
    y_back = lax.all_to_all(y_send, axis_name, split_axis=0,
                            concat_axis=0, tiled=True)
    # scatter results back to assignment slots, then weight + reduce K
    src = meta[..., 0].reshape(ep * capacity)            # original meta
    y_flat = y_back.reshape(ep * capacity, d)
    out_assign = jnp.zeros((TK + 1, d), jnp.float32)
    out_assign = out_assign.at[src].add(y_flat.astype(jnp.float32))
    out_assign = out_assign[1:]                          # drop the 0 slot
    out = (out_assign.reshape(T_l, K, d)
           * top_w[..., None].astype(jnp.float32)).sum(axis=1)
    return out.astype(xt.dtype)


def moe_all_to_all_sharded(mesh: Mesh, xt, top_e, top_w, expert_weights,
                           activation_fn: Callable, *, n_experts: int,
                           axis_name: str = "model",
                           capacity_factor: float = 2.0):
    """shard_map wrapper: xt (T, d) sharded over ``axis_name``; expert
    weight arrays have leading dim E sharded over ``axis_name``."""

    def body(xt_l, e_l, w_l, *weights_l):
        def expert_fn(local_eid, x):
            return activation_fn(local_eid, x, weights_l)
        return moe_all_to_all(xt_l, e_l, w_l, expert_fn,
                              n_experts=n_experts, axis_name=axis_name,
                              capacity_factor=capacity_factor)

    pspec_tok = P(axis_name)
    pspec_w = P(axis_name)
    flat_w = jax.tree.leaves(expert_weights)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(pspec_tok, pspec_tok, pspec_tok)
                       + tuple(pspec_w for _ in flat_w),
                       out_specs=pspec_tok)
    return fn(xt, top_e, top_w, *flat_w)
