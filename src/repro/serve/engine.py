"""Continuous-batching serving engine with a sync-free decode hot path.

A fixed pool of batch slots; a finished sequence frees its slot and the
next queued request is prefilled into that slot *mid-decode*, without
draining the batch (the setting of the paper's §10 inference outlook —
Kakolyris/DynamoLLM operate here).  Responsibilities split three ways:

* :class:`~repro.serve.scheduler.Scheduler` — admission queue + slot
  lifecycle (host-side bookkeeping only),
* :class:`~repro.serve.batch_state.BatchState` /
  :class:`~repro.serve.kv_pages.PagedBatchState` — pooled caches,
  positions, on-device generation budgets (device-side state),
* ``ServeEngine`` (here) — the jitted model math.

The hot path is **sync-free within a round**:

1. *Batched bucketed admission* — all requests admitted this round are
   grouped by power-of-two prompt bucket and prefilled in **one jit call
   per bucket** (rows padded to a fixed width, per-row ``prompt_lens``
   masking inside the model).  Slot activation (tokens/pos/remaining
   scatters) happens inside the same call; the sampled first tokens are
   fetched lazily at the next round sync.
2. *On-device termination* — the per-slot budget ``remaining`` rides the
   ``lax.scan`` carry of every decode chunk: a slot that hits its max-len
   or samples ``eos_token`` freezes in place (tokens/pos held, no more
   emissions) with no host involvement.
3. *Multi-chunk rounds* — ``_decode_round`` dispatches several chunks
   back-to-back (JAX dispatch is async) and performs a **single
   ``device_get`` per round** for the stacked (tokens, emitted-mask)
   pairs + pending first tokens, instead of one blocking ``np.asarray``
   + Python token loop per chunk.

All jitted entry points donate the cache (and the slot vectors), so
device buffers update in place; jitted callables are memoized per
(chunk-len | prompt-bucket) and surfaced via :attr:`compile_stats`.

When given a :class:`~repro.dvfs.ServeGovernorExecutor` (usually from
:meth:`~repro.dvfs.DvfsSession.serve_executor`), the engine replays the
governor's :class:`~repro.dvfs.DvfsPlan` around every phase transition
(prefill vs decode, bucketed by active-slot count) — the plan → runtime
loop, closed.
An :class:`~repro.dvfs.OnlineGovernor` additionally re-plans the decode
segments when the observed bucket mix drifts from the planned one.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .batch_state import BatchState
from .kv_pages import (PagedBatchState, cow_copy_block, scale_key,
                       write_prefill_pages)
from .scheduler import Scheduler
from ..cache import RadixCache, extras_namespace
from ..models import common as cm
from ..obs import NULL_TRACER


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 16
    generated: List[int] = field(default_factory=list)
    done: bool = False
    # engine decode-step counter at completion (latency-in-steps metric)
    finished_step: Optional[int] = None
    # family-specific prefill inputs (encdec: {"frames": ...};
    # vlm: {"patch_embeds": ...})
    extras: Dict[str, Any] = field(default_factory=dict)


def sample_token(logits: jnp.ndarray, rng, temperature: float = 0.0):
    """Greedy (T=0) or temperature sampling; logits (B, V)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature, axis=-1) \
        .astype(jnp.int32)


def _chunk_len(n: int, cap: int) -> int:
    """Largest power of two <= min(n, cap): bounds both over-decode and
    jit recompiles (log2 distinct scan lengths)."""
    n = min(n, cap)
    p = 1
    while 2 * p <= n:
        p *= 2
    return p


def _bucket(plen: int) -> int:
    """Smallest power of two >= plen (>= 8, so tiny prompts share one
    compile variant)."""
    b = 8
    while b < plen:
        b *= 2
    return b


class ServeEngine:
    """Single-host continuous-batching engine over a repro model."""

    def __init__(self, model, params, batch_slots: int = 4,
                 max_seq: int = 512, temperature: float = 0.0,
                 seed: int = 0, executor=None, max_chunk: int = 16,
                 eos_token: Optional[int] = None, paged: bool = False,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 prefix_cache: bool = False, tracer=None):
        self.model = model
        self.params = params
        # engine timeline is the jitted decode-step counter (modeled,
        # deterministic); NullTracer keeps the hot path branch-cheap
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.trace_track = "serve"
        self.slots = batch_slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.seed = seed
        self.rng = jax.random.PRNGKey(seed)
        self.executor = executor
        self.max_chunk = max_chunk
        self.eos_token = eos_token
        self.paged = paged
        self.page_size = page_size
        self.n_pages = n_pages
        self.kv_dtype = kv_dtype
        if paged and max_seq % page_size:
            raise ValueError(f"paged engine needs max_seq ({max_seq}) to "
                             f"be a multiple of page_size ({page_size})")
        if kv_dtype not in (None, "none") and not paged:
            raise ValueError("kv_dtype quantization needs paged=True "
                             "(only page pools carry scale tables)")
        if prefix_cache and not paged:
            raise ValueError("prefix_cache needs paged=True (sharing is "
                             "a block-table splice)")
        # radix prefix index over the page pool: admission splices cached
        # prefix pages read-only into the slot's block table (divergent
        # tail pages copy-on-write), and finished prefills adopt their
        # fully-valid pages into the tree
        self.prefix_cache: Optional[RadixCache] = \
            RadixCache(page_size, seed=seed) if prefix_cache else None
        self._slot_shared: Dict[int, int] = {}  # slot -> spliced full pages
        self.scheduler = Scheduler(batch_slots)
        self.state = self._new_state()
        self.n_decode_steps = 0           # jitted chunk-steps executed
        # memoized jitted entry points; keys are the only shape-varying
        # dims (decode chunk length / prompt bucket), so compile count is
        # bounded by log2(max_chunk) + n_buckets — see compile_stats
        self._chunk_fns: Dict[int, Any] = {}
        self._prefill_fns: Dict[int, Any] = {}
        # admissions whose sampled first token has not been fetched yet:
        # (admit_step, [(slot, request), ...], device array of firsts)
        self._pending_first: List[Tuple[int, List, jnp.ndarray]] = []

    def _new_state(self):
        if self.paged:
            return PagedBatchState(self.model, self.slots, self.max_seq,
                                   page_size=self.page_size,
                                   n_pages=self.n_pages,
                                   kv_dtype=self.kv_dtype)
        return BatchState(self.model, self.slots, self.max_seq)

    def reset(self) -> None:
        """Clear serving state for a fresh workload; jitted functions (and
        their compile caches) survive — steady-state benchmarking."""
        self.rng = jax.random.PRNGKey(self.seed)
        self.scheduler = Scheduler(self.slots)
        self.state = self._new_state()
        self.n_decode_steps = 0
        self._pending_first = []
        if self.prefix_cache is not None:
            self.prefix_cache = RadixCache(self.page_size, seed=self.seed)
        self._slot_shared = {}
        if self.executor is not None:
            self.executor.reset()

    @property
    def compile_stats(self) -> Dict[str, int]:
        """Jit variant counts of the two hot-path entry points."""
        d, p = len(self._chunk_fns), len(self._prefill_fns)
        return {"decode_chunk_variants": d, "prefill_bucket_variants": p,
                "n_variants": d + p}

    # -- jitted entry points ---------------------------------------------
    def _decode_impl(self, params, cache, tokens, pos, remaining, rng,
                     tables=None, *, n: int):
        """Scan ``n`` decode steps over every slot with on-device
        termination; emits (tokens, generated-mask) per step.  The RNG
        advances *inside* the call (returned as carry), so the host never
        dispatches key splits on the hot path."""
        temperature = self.temperature
        eos = self.eos_token
        rng, sub = jax.random.split(rng)
        keys = jax.random.split(sub, n)

        def step(carry, key):
            tokens, pos, cache, rem = carry
            logits, cache = self.model.decode_step(params, cache, tokens,
                                                   pos, block_tables=tables)
            nxt = sample_token(logits, key, temperature)
            gen = rem > 0
            # finished slots freeze: same token re-fed at the same pos is
            # idempotent for every cache family, and the row is fully
            # overwritten at the next admission
            nxt = jnp.where(gen, nxt, tokens)
            rem = jnp.where(gen, rem - 1, rem)
            if eos is not None:
                rem = jnp.where(gen & (nxt == eos), 0, rem)
            pos = jnp.where(gen, pos + 1, pos)
            return (nxt, pos, cache, rem), (nxt, gen)

        (tokens, pos, cache, remaining), (toks, gens) = lax.scan(
            step, (tokens, pos, cache, remaining), keys)
        return tokens, pos, cache, remaining, rng, toks, gens

    def _prefill_impl(self, params, cache, tokens_st, pos_st, rem_st,
                      prompts, meta, rng, tables_sub=None, **extras):
        """One bucket's batched admission: masked batched prefill, cache
        install (slot rows or pages), and slot activation — one jit call.

        ``meta`` packs (prompt_lens, slots, budgets) as one (3, N) int32
        transfer.  Rows are padded to a fixed width; dummy rows carry
        ``slot == n_slots``/out-of-range page ids and are dropped by every
        scatter.
        """
        plens, slots, budgets = meta[0], meta[1], meta[2]
        prefix = extras["patch_embeds"].shape[1] \
            if "patch_embeds" in extras else 0
        logits, sub = self.model.prefill(
            params, prompts, prompt_lens=plens, max_seq=self.max_seq,
            remat=False, **extras)
        rng, key = jax.random.split(rng)
        first = sample_token(logits, key, self.temperature)
        axes = self.model.cache_slot_axes()
        if tables_sub is not None:
            paged_keys = set(self.model.paged_cache_keys())
            scale_keys = {scale_key(k) for k in paged_keys}
            new_cache = {}
            for k in cache:
                if k in paged_keys:
                    sk = scale_key(k)
                    if sk in cache:
                        # quantized pool: the page write derives fresh
                        # per-(page, KV-head) scales alongside the payload
                        new_cache[k], new_cache[sk] = write_prefill_pages(
                            cache[k], sub[k], tables_sub,
                            scales=cache[sk],
                            qmax=cm.kv_qmax(cache[k].dtype))
                    else:
                        new_cache[k] = write_prefill_pages(
                            cache[k], sub[k], tables_sub)
                elif k in scale_keys:
                    pass              # written alongside its base leaf
                else:
                    new_cache[k] = cm.write_cache_slots(
                        {k: cache[k]}, {k: sub[k]}, slots,
                        {k: axes[k]})[k]
            cache = new_cache
        else:
            cache = cm.write_cache_slots(cache, sub, slots, axes)
        rem = budgets - 1
        if self.eos_token is not None:
            rem = jnp.where(first == self.eos_token, 0, rem)
        tokens_st = tokens_st.at[slots].set(first, mode="drop")
        pos_st = pos_st.at[slots].set(plens + prefix, mode="drop")
        rem_st = rem_st.at[slots].set(rem, mode="drop")
        return first, cache, tokens_st, pos_st, rem_st, rng

    def _chunk_fn(self, n: int):
        fn = self._chunk_fns.get(n)
        if fn is None:
            fn = jax.jit(functools.partial(self._decode_impl, n=n),
                         donate_argnums=(1, 2, 3, 4, 5))
            self._chunk_fns[n] = fn
        return fn

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = jax.jit(self._prefill_impl,
                         donate_argnums=(1, 2, 3, 4, 7))
            self._prefill_fns[bucket] = fn
        return fn

    # -- admission -------------------------------------------------------
    def _req_prefix(self, req: Request) -> int:
        pe = req.extras.get("patch_embeds")
        return 0 if pe is None else pe.shape[1]

    def _cache_key(self, req: Request) -> Tuple[int, List[int]]:
        """(namespace, position-token stream) identifying the request's
        cache pages.  Extras (encoder frames, patch embeds) shift or
        condition every K/V position, so they pick the namespace; a
        vision prefix contributes sentinel positions (its content is
        pinned by the namespace), then the prompt ids follow."""
        ns = extras_namespace(req.extras)
        return ns, [-1] * self._req_prefix(req) \
            + [int(t) for t in np.asarray(req.prompt, np.int64)]

    def _prefix_match(self, req: Request, need: int):
        """Cached pages spliceable into a ``need``-token reservation:
        ``(full_pages, tail_hit)`` with the tail (a partially matching
        page the request will overwrite past the match) only taken when
        the reservation has a block left for its copy."""
        if self.prefix_cache is None:
            return [], None
        ns, key = self._cache_key(req)
        pages, _, tail = self.prefix_cache.match(key, ns=ns, tail=True)
        need_pages = max(-(-need // self.page_size), 1)
        if len(pages) > need_pages:         # defensive: cannot trigger,
            pages, tail = pages[:need_pages], None   # matched <= need
        if tail is not None and len(pages) + 1 > need_pages:
            tail = None
        return pages, tail

    def _allocate_paged(self, slot: int, req: Request, need: int) -> bool:
        """Reserve ``slot``'s pages, splicing any cached prefix; on pool
        pressure, evict cold tree-only pages and retry before deferring.
        A tail (partial-page) hit is copy-on-write-resolved immediately:
        the divergent suffix write span is already known at admission,
        so the copy happens here rather than via a per-token fault."""
        pool = self.state.pool
        shared, tail = self._prefix_match(req, need)
        splice = list(shared) + ([tail[0]] if tail is not None else [])
        need_pages = max(-(-need // self.page_size), 1)
        fresh = need_pages - len(splice)
        extra = 0 if tail is None else 1        # the CoW copy target page
        if self.prefix_cache is not None and pool.n_free < fresh + extra:
            self.prefix_cache.evict(pool, fresh + extra - pool.n_free)
        if tail is not None and pool.n_free < fresh + 1:
            # no page left for the copy: fall back to a plain full-page
            # splice (the prefill recomputes the tail anyway)
            tail, splice = None, list(shared)
        ok = pool.allocate(slot, need, shared=splice)
        if not ok:
            if not int(pool.n_blocks.sum()):  # no slot holds pages: the
                # request can never fit, backpressure would deadlock
                raise ValueError(
                    f"request {req.uid} needs {need} tokens; the "
                    f"page pool holds "
                    f"{pool.n_free * pool.page_size} usable")
            return False
        self._slot_shared[slot] = len(shared)
        if tail is not None:
            cow_copy_block(self.state, slot, len(shared))
        if self.tracer.enabled and (shared or tail is not None):
            self.tracer.instant(
                self.trace_track, "prefix-hit",
                float(self.n_decode_steps), cat="cache",
                args={"uid": req.uid, "shared_pages": len(shared),
                      "cow_tail": tail is not None, "slot": slot})
        return True

    def _admit(self) -> None:
        """Admit every admissible queued request, bucketed by prompt
        length: one jitted (prefill + install + activate) call per
        power-of-two bucket.  Paged mode allocates each request's pages
        here (whole request up front — the decode path never allocates);
        a request that does not fit re-queues at the head and admission
        stops (backpressure)."""
        admitted: List[Tuple[int, Request]] = []
        while True:
            nxt = self.scheduler.admit_next()
            if nxt is None:
                break
            slot, req = nxt
            if req.max_new_tokens < 1:
                # nothing to generate: complete without touching the pool
                req.done = True
                req.finished_step = self.n_decode_steps
                self.scheduler.release(slot)
                continue
            prompt = np.asarray(req.prompt, np.int32)
            prefix = self._req_prefix(req)
            if prefix + prompt.size + req.max_new_tokens > self.max_seq + 1:
                raise ValueError(
                    f"request {req.uid}: prompt {prefix + prompt.size} + "
                    f"{req.max_new_tokens} new tokens exceeds "
                    f"max_seq={self.max_seq}")
            if self.paged:
                # positions written: prompt 0..P-1, decode P..P+new-2 (the
                # final sampled token is emitted, never cached); a frozen
                # slot's parked re-write one past that lands in the
                # parking page if its block is unallocated
                need = prefix + prompt.size + req.max_new_tokens - 1
                if not self._allocate_paged(slot, req, need):
                    # pool exhausted: undo this admission, wait for frees
                    self.scheduler.requeue(slot)
                    break
            admitted.append((slot, req))
        if not admitted:
            return
        if self.paged:
            self.state.sync_tables()
        # one jit call per (prompt bucket, extras signature): rows of a
        # batch must stack, so requests with different extras keys or
        # shapes (e.g. text-only next to patch_embeds) go in separate
        # calls rather than silently dropping or mis-stacking an input.
        # The bucket caps at the cache's remaining room (max_seq minus any
        # vision prefix) — prompts near max_seq must not pad past it.
        groups: Dict[Tuple, List[Tuple[int, Request]]] = {}
        for slot, req in admitted:
            sig = tuple(sorted((k, np.asarray(v).shape)
                               for k, v in req.extras.items()))
            b = min(_bucket(len(req.prompt)),
                    self.max_seq - self._req_prefix(req))
            groups.setdefault((b, sig), []).append((slot, req))
        for key in sorted(groups, key=str):
            self._admit_bucket(key[0], groups[key])

    def _admit_bucket(self, bucket: int,
                      pairs: List[Tuple[int, Request]]) -> None:
        N = self.slots                      # fixed row count per bucket
        prompts = np.zeros((N, bucket), np.int32)
        meta = np.ones((3, N), np.int32)    # (plens, slots, budgets)
        meta[1] = self.slots                # dummy rows: OOB -> dropped
        for i, (slot, req) in enumerate(pairs):
            p = np.asarray(req.prompt, np.int32)
            prompts[i, :p.size] = p
            meta[0, i] = p.size
            meta[1, i] = slot
            meta[2, i] = req.max_new_tokens
        extras: Dict[str, jnp.ndarray] = {}
        for key, val in pairs[0][1].extras.items():
            rows = [np.asarray(r.extras[key])[0] for _, r in pairs]
            pad = np.zeros_like(rows[0])
            extras[key] = jnp.asarray(
                np.stack(rows + [pad] * (N - len(pairs))))
        args = [self.params, self.state.cache, self.state.tokens,
                self.state.pos, self.state.remaining,
                jnp.asarray(prompts), jnp.asarray(meta), self.rng]
        if self.paged:
            pool = self.state.pool
            tables_sub = np.full((N, pool.max_blocks), pool.n_pages,
                                 np.int32)                # OOB -> dropped
            for i, (slot, _) in enumerate(pairs):
                nb = int(pool.n_blocks[slot])
                tables_sub[i, :nb] = pool.tables[slot, :nb]
                # spliced prefix pages are shared read-only: this row's
                # prefill re-derives their K/V bit-identically, so the
                # redundant writes (and scale updates) are dropped by
                # pointing them out of range.  Decode reads still see the
                # real ids through the device block tables.
                ns = self._slot_shared.pop(slot, 0)
                tables_sub[i, :ns] = pool.n_pages
            args.append(jnp.asarray(tables_sub))
        if self.executor is not None:
            for _ in pairs:
                self.executor.on_prefill()
        if self.tracer.enabled:
            for slot, req in pairs:
                self.tracer.instant(
                    self.trace_track, "admit",
                    float(self.n_decode_steps), cat="lifecycle",
                    args={"uid": req.uid, "slot": slot, "bucket": bucket,
                          "prompt_len": len(req.prompt)})
        (first, self.state.cache, self.state.tokens, self.state.pos,
         self.state.remaining, self.rng) = \
            self._prefill_fn(bucket)(*args, **extras)
        if self.prefix_cache is not None:
            # adopt every fully-valid prompt page (positions < prefix +
            # prompt only; the decode span never enters the tree) —
            # shared head chunks are already nodes, fresh tails retain
            pool = self.state.pool
            for slot, req in pairs:
                ns, key = self._cache_key(req)
                n_full = len(key) // self.page_size
                if n_full:
                    self.prefix_cache.insert(
                        key, [int(p) for p in pool.tables[slot, :n_full]],
                        pool, ns=ns)
        self._pending_first.append((self.n_decode_steps, list(pairs),
                                    first))

    # -- decode ----------------------------------------------------------
    def _decode_round(self) -> None:
        """Dispatch this round's decode chunks asynchronously, then sync
        once: fetch pending first tokens + every chunk's (tokens, mask)
        stack, extend requests, release finished slots."""
        live = [(s, r) for s, r in enumerate(self.scheduler.slots)
                if r is not None]
        pend_slots = {s for _, ps, _ in self._pending_first for s, _ in ps}
        ubs = [r.max_new_tokens - len(r.generated)
               - (1 if s in pend_slots else 0) for s, r in live]
        positive = [u for u in ubs if u > 0]
        if not positive and not self._pending_first:
            if live:
                raise RuntimeError("stalled: live slots with no budget "
                                   "and nothing pending")
            return
        # never outrun the soonest slot release while admissions wait;
        # drain at full chunk width when the queue is empty (idle slots
        # cost nothing — the scan always covers the whole pool)
        bound = 0
        if positive:
            bound = min(positive) if self.scheduler.pending \
                else max(positive)
        chunks: List[Tuple[int, Any, Any]] = []
        st = self.state
        off = 0                      # steps already dispatched this round
        while bound > 0:
            n = _chunk_len(bound, self.max_chunk)
            if self.executor is not None:
                # expected occupancy per step from the host-known budgets
                # (exact for max-len termination; upper bound under EOS)
                for step in range(off, off + n):
                    self.executor.on_decode(
                        sum(1 for u in ubs if u > step))
            args = (self.params, st.cache, st.tokens, st.pos, st.remaining,
                    self.rng)
            if self.paged:
                out = self._chunk_fn(n)(*args, st.tables_dev)
            else:
                out = self._chunk_fn(n)(*args)
            (st.tokens, st.pos, st.cache, st.remaining, self.rng,
             toks, gens) = out
            chunks.append((self.n_decode_steps, toks, gens))
            self.n_decode_steps += n
            bound -= n
            off += n
        if self.tracer.enabled and off:
            self.tracer.span(
                self.trace_track, "decode-round",
                float(self.n_decode_steps - off), float(off), cat="phase",
                args={"steps": off, "chunks": len(chunks),
                      "live": len(live)})
        self._sync(chunks)

    def _sync(self, chunks) -> None:
        """The round's single host round-trip."""
        pending, self._pending_first = self._pending_first, []
        if not pending and not chunks:
            return
        firsts, fetched = jax.device_get(
            ([f for _, _, f in pending], [(t, g) for _, t, g in chunks]))
        last_step: Dict[int, int] = {}
        for (admit_step, pairs, _), first in zip(pending, firsts):
            for i, (slot, req) in enumerate(pairs):
                req.generated.append(int(first[i]))
                last_step[slot] = admit_step
        for (step0, _, _), (toks, gens) in zip(chunks, fetched):
            for slot, req in enumerate(self.scheduler.slots):
                if req is None:
                    continue
                hit = np.nonzero(gens[:, slot])[0]
                if hit.size:
                    req.generated.extend(int(t)
                                         for t in toks[hit, slot])
                    last_step[slot] = step0 + int(hit[-1]) + 1
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                continue
            full = len(req.generated) >= req.max_new_tokens
            eosd = (self.eos_token is not None and req.generated
                    and req.generated[-1] == self.eos_token)
            if full or eosd:
                req.done = True
                req.finished_step = last_step.get(slot,
                                                  self.n_decode_steps)
                self.scheduler.release(slot)
                if self.paged:
                    self.state.pool.free(slot)

    # -- driving ---------------------------------------------------------
    def submit(self, requests: List[Request]) -> None:
        self.scheduler.submit(requests)

    def run(self) -> None:
        """Drain the queue: admit into free slots, decode in rounds."""
        while not self.scheduler.done():
            self._admit()
            self._decode_round()
        if self.executor is not None:
            self.executor.finish()

    def generate(self, requests: List[Request]) -> List[Request]:
        self.submit(requests)
        self.run()
        return requests

    def energy_summary(self) -> Optional[Dict]:
        return None if self.executor is None else self.executor.summary()

    def prefix_cache_stats(self) -> Optional[Dict]:
        """Radix-tree hit/occupancy counters plus the pool's sharing
        life-cycle counters; None when the cache is off."""
        if self.prefix_cache is None:
            return None
        ps = self.state.pool.stats()
        return {**self.prefix_cache.stats(),
                "shared_pages": ps["shared_pages"],
                "cow_copies": ps["cow_copies"],
                "evictions": ps["evictions"]}
