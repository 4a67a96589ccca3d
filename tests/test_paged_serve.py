"""Sync-free serve hot path: page-pool invariants, paged-vs-dense parity
across every model family, on-device EOS termination, jit-variant budgets,
buffer donation, and the vectorized planner's equivalence to the scalar
reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FAMILY_ARCHS
from conftest import make_requests as _requests
from conftest import smoke_model as _smoke
from repro.serve import PagePool, Request, ServeEngine


# ---------------------------------------------------------------------------
# page pool invariants
# ---------------------------------------------------------------------------

def test_page_pool_allocate_free_invariants():
    pool = PagePool(n_pages=9, page_size=4, n_slots=3, max_blocks=4)
    assert pool.n_free == 8               # page 0 is reserved parking
    assert pool.allocate(0, 9)            # 3 pages (ceil(9/4))
    assert pool.n_free == 5 and pool.n_blocks[0] == 3
    # allocated pages are distinct, in range, and never the parking page
    pages = set(pool.tables[0, :3].tolist())
    assert len(pages) == 3 and all(1 <= p < 9 for p in pages)
    with pytest.raises(ValueError):       # double allocation forbidden
        pool.allocate(0, 1)
    assert pool.allocate(1, 16)           # 4 pages
    assert not pool.allocate(2, 8)        # 1 free < 2 needed -> defer
    assert pool.n_free == 1               # failed alloc takes nothing
    pool.free(0)
    assert pool.n_free == 4
    # freed rows point back at parking (frozen-slot writes stay harmless)
    assert set(pool.tables[0].tolist()) == {0}
    with pytest.raises(ValueError):       # double free forbidden
        pool.free(0)
    assert pool.allocate(2, 8)            # deferred request now fits
    # full drain restores the complete free list (parking excluded)
    pool.free(1)
    pool.free(2)
    assert pool.n_free == 8
    assert sorted(pool._free) == list(range(1, 9))


def test_page_pool_randomized_stress():
    """Satellite invariant sweep, now with prefix sharing: long
    interleaved admit/retire/requeue/adopt/drop/splice/CoW sequences
    must never double-allocate a page, leak one, corrupt a refcount, or
    hand out the reserved parking page 0 — and the adversarial
    interleavings (double-release of a shared page past refcount zero,
    eviction of a page a slot still maps) must raise, not corrupt."""
    rng = np.random.default_rng(0)
    n_pages, page_size, n_slots, max_blocks = 33, 4, 6, 8
    pool = PagePool(n_pages=n_pages, page_size=page_size,
                    n_slots=n_slots, max_blocks=max_blocks)
    held = {}                             # slot -> block-table page list
    tree = set()                          # pages a simulated radix tree
    #                                     # holds one retain each on

    def check():
        # reference refcounts: slots mapping the page + the tree retain
        model = {}
        for pages in held.values():
            assert len(pages) == len(set(pages))  # per-slot distinct
            for p in pages:
                model[p] = model.get(p, 0) + 1
        for p in tree:
            model[p] = model.get(p, 0) + 1
        live = set(model)
        # none of them parking, none leaked, none double-freed
        assert 0 not in live
        assert all(1 <= p < n_pages for p in live)
        assert pool.n_free + len(live) == n_pages - 1
        assert sorted(set(pool._free)) == sorted(pool._free)
        assert set(pool._free).isdisjoint(live) and 0 not in pool._free
        for p in range(n_pages):
            assert int(pool.refcounts[p]) == model.get(p, 0), p
        for slot in range(n_slots):
            n = int(pool.n_blocks[slot])
            assert pool.tables[slot, :n].tolist() == held.get(slot, [])
            # unallocated tail always points at parking
            assert set(pool.tables[slot, n:].tolist()) <= {0}

    def grab(slot, want, shared=()):
        if pool.allocate(slot, want, shared=shared):
            n = int(pool.n_blocks[slot])
            held[slot] = pool.tables[slot, :n].tolist()

    for i in range(2000):
        op = rng.integers(7)
        if op == 0:                       # admit into a free slot
            free = [s for s in range(n_slots) if s not in held]
            if free:
                grab(int(rng.choice(free)),
                     int(rng.integers(1, max_blocks * page_size + 1)))
        elif op == 1 and held:            # retire a finished request
            slot = int(rng.choice(list(held)))
            pool.free(slot)
            del held[slot]
        elif op == 2 and held:            # backpressure: undo admission
            slot = int(rng.choice(list(held)))
            pool.free(slot)               # engine requeue frees the slot
            del held[slot]
            # the retried request may need a different page count
            grab(slot, int(rng.integers(1, max_blocks * page_size + 1)))
        elif op == 3:                     # radix adoption: retain a live
            cand = [p for pages in held.values() for p in pages
                    if p not in tree]
            if cand:
                p = int(rng.choice(cand))
                v = pool.version          # pure refcount motion: the
                pool.retain_page(p)       # device-mirror fast path holds
                assert pool.version == v
                tree.add(p)
        elif op == 4 and tree:            # eviction/flush drops a retain
            p = int(rng.choice(sorted(tree)))
            v = pool.version
            pool.release_page(p)
            assert pool.version == v
            tree.discard(p)
        elif op == 5 and tree:            # prefix splice: shared admit
            free = [s for s in range(n_slots) if s not in held]
            if free:
                k = int(rng.integers(1, min(len(tree), max_blocks) + 1))
                shared = [int(p) for p in
                          rng.choice(sorted(tree), size=k, replace=False)]
                lo = max((k - 1) * page_size + 1, 1)
                grab(int(rng.choice(free)),
                     int(rng.integers(lo, max_blocks * page_size + 1)),
                     shared=shared)
        elif op == 6 and held and pool._free:   # CoW a shared block
            slot = int(rng.choice(list(held)))
            blocks = [b for b, p in enumerate(held[slot])
                      if pool.refcounts[p] > 1]
            if blocks:
                block = int(rng.choice(blocks))
                out = pool.cow(slot, block)
                assert out is not None
                held[slot][block] = out[1]
        if i % 97 == 0:                   # adversarial: must raise, not
            if pool._free:                # corrupt the free list
                with pytest.raises(ValueError):   # release past zero —
                    pool.release_page(int(pool._free[-1]))  # the double
                    # release of a page whose sharers all already let go
            pinned = [p for p in range(1, n_pages)
                      if pool.refcounts[p] > 1]
            if pinned:                    # a mapped page is never evicted
                with pytest.raises(ValueError):
                    pool.evict_page(int(rng.choice(pinned)))
        check()
    for slot in list(held):
        pool.free(slot)
        del held[slot]
    for p in sorted(tree):
        pool.release_page(p)
    tree.clear()
    check()
    assert pool.n_free == n_pages - 1     # drained: nothing leaked


def test_page_pool_fragmentation_stats():
    pool = PagePool(n_pages=9, page_size=4, n_slots=2, max_blocks=4)
    pool.allocate(0, 5)                   # 2 pages for 5 tokens
    s = pool.stats()
    assert s["allocated_pages"] == 2 and s["used_tokens"] == 5
    assert s["internal_frag_tokens"] == 3  # 8-token capacity, 5 needed
    assert 0 < s["internal_frag_frac"] < 1
    pool.free(0)
    assert pool.stats()["internal_frag_tokens"] == 0


def test_page_pool_oversize_request_raises():
    pool = PagePool(n_pages=8, page_size=4, n_slots=2, max_blocks=2)
    with pytest.raises(ValueError):
        pool.allocate(0, 100)             # wider than the block table


# ---------------------------------------------------------------------------
# paged vs dense: exact logits parity, every family
# ---------------------------------------------------------------------------

_HEAVY = [pytest.param("hybrid", marks=pytest.mark.slow),
          pytest.param("encdec", marks=pytest.mark.slow)]


@pytest.mark.parametrize("family", ["transformer", "ssm"] + _HEAVY)
def test_paged_vs_dense_decode_logits(family):
    """Admit the same prompts into a dense slot pool and a page pool, then
    single-step both caches for several tokens: logits parity <= 1e-5."""
    model, params, cfg = _smoke(FAMILY_ARCHS[family])
    reqs = _requests(cfg, n=2)[:2]
    dense = ServeEngine(model, params, batch_slots=2, max_seq=64)
    paged = ServeEngine(model, params, batch_slots=2, max_seq=64,
                        paged=True, page_size=16)
    for eng in (dense, paged):
        eng.submit([dataclasses.replace(r, generated=[]) for r in reqs])
        eng._admit()
    dstep = jax.jit(lambda c, t, q: model.decode_step(params, c, t, q))
    pstep = jax.jit(lambda c, t, q, tb: model.decode_step(
        params, c, t, q, block_tables=tb))
    dtok, dpos = dense.state.tokens, dense.state.pos
    ptok, ppos = paged.state.tokens, paged.state.pos
    assert np.array_equal(np.asarray(dtok), np.asarray(ptok))
    dcache, pcache = dense.state.cache, paged.state.cache
    for _ in range(3):
        ld, dcache = dstep(dcache, dtok, dpos)
        lp, pcache = pstep(pcache, ptok, ppos, paged.state.tables_dev)
        assert float(jnp.max(jnp.abs(ld - lp))) <= 1e-5, family
        dtok = ptok = jnp.argmax(ld, -1).astype(jnp.int32)
        dpos = dpos + 1
        ppos = ppos + 1


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(FAMILY_ARCHS.values())
                         + ["llama4-scout-17b-a16e", "internvl2-1b"])
def test_paged_engine_matches_dense_engine(arch):
    """Full engine runs (slot reuse, batched admission, page recycling)
    produce identical greedy tokens paged vs dense."""
    model, params, cfg = _smoke(arch)
    d = ServeEngine(model, params, batch_slots=2,
                    max_seq=64).generate(_requests(cfg))
    p = ServeEngine(model, params, batch_slots=2, max_seq=64, paged=True,
                    page_size=16).generate(_requests(cfg))
    for x, y in zip(d, p):
        assert x.generated == y.generated, (arch, x.uid)


@pytest.mark.slow
def test_paged_pool_backpressure_serves_everything():
    """A pool too small for all slots at once defers admissions instead of
    corrupting state; every request still completes with exact tokens."""
    model, params, cfg = _smoke("llama3.2-1b")
    ref = ServeEngine(model, params, batch_slots=2,
                      max_seq=64).generate(_requests(cfg))
    # 2 usable pages of 16 (+1 parking) = one ~2-page request at a time
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64, paged=True,
                      page_size=16, n_pages=3)
    out = eng.generate(_requests(cfg))
    for r in out:
        assert r.done
    # single-slot-at-a-time scheduling can reorder completions but not
    # change each request's greedy continuation
    for x, y in zip(out, ref):
        assert x.generated == y.generated, x.uid
    assert eng.state.pool.n_free == eng.state.pool.n_pages - 1  # parking


# ---------------------------------------------------------------------------
# batched bucketed prefill == single prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["transformer", "ssm"] + _HEAVY)
def test_batched_bucketed_prefill_matches_single(family):
    """Right-padded bucket prefill with prompt_lens masking matches
    per-prompt prefill for every family: same greedy token, logits within
    1e-5.  Not bit-exact: XLA's float32 reduction order changes with the
    batch shape, and the measured difference is at most 2.2e-7 — a leak
    through the padding mask would be orders of magnitude larger."""
    model, params, cfg = _smoke(FAMILY_ARCHS[family])
    rng = np.random.default_rng(4)
    plens = [5, 9, 12]
    prompts = np.zeros((3, 16), np.int32)
    singles = []
    for i, L in enumerate(plens):
        p = rng.integers(0, cfg.vocab_size, L)
        prompts[i, :L] = p
        singles.append(p)
    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = jnp.asarray(rng.normal(
            size=(3, cfg.encoder_frontend_len, cfg.d_model)), jnp.float32)
    kw = {"max_seq": 48}
    logits_b, _ = model.prefill(params, jnp.asarray(prompts), remat=False,
                                prompt_lens=jnp.asarray(plens, jnp.int32),
                                **extras, **kw)
    for i, p in enumerate(singles):
        ex = {k: v[i:i + 1] for k, v in extras.items()}
        l1, _ = model.prefill(params, jnp.asarray(p[None]), remat=False,
                              **ex, **kw)
        got, want = np.asarray(logits_b[i]), np.asarray(l1[0])
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                                   err_msg=f"{family} row {i}")
        assert got.argmax() == want.argmax(), (family, i)


# ---------------------------------------------------------------------------
# on-device EOS termination
# ---------------------------------------------------------------------------

_JITTED_STEPS = {}


def _manual_greedy(model, params, prompt, max_new, eos=None, max_seq=64):
    if id(model) not in _JITTED_STEPS:
        _JITTED_STEPS[id(model)] = jax.jit(
            lambda p, c, t, q: model.decode_step(p, c, t, q))
    step = _JITTED_STEPS[id(model)]
    t = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, cache = model.prefill(params, t, max_seq=max_seq, remat=False)
    cur = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [int(cur[0])]
    while len(out) < max_new and (eos is None or out[-1] != eos):
        pos = jnp.asarray([len(prompt) + len(out) - 1], jnp.int32)
        logits, cache = step(params, cache, cur, pos)
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(int(cur[0]))
    return out


def test_on_device_eos_matches_host_reference():
    """Pick a token the greedy model emits mid-stream as the EOS id: the
    engine (which only learns of it on device, mid-chunk) must truncate
    exactly where the host-side reference loop stops."""
    model, params, cfg = _smoke("llama3.2-1b")
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, L) for L in (5, 9, 12, 7)]
    full = [_manual_greedy(model, params, p, 24) for p in prompts]
    # an EOS id that appears mid-generation in at least one stream
    eos = next(t for stream in full for t in stream[2:-1])
    hit = sum(eos in s for s in full)
    assert hit >= 1
    refs = [_manual_greedy(model, params, p, 24, eos=eos) for p in prompts]
    assert any(len(r) < len(f) for r, f in zip(refs, full))  # mid-chunk cut
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64,
                      eos_token=int(eos))
    out = eng.generate([Request(uid=i, prompt=p, max_new_tokens=24)
                        for i, p in enumerate(prompts)])
    for r, ref in zip(out, refs):
        assert r.generated == ref, r.uid
        assert r.done and r.finished_step is not None


def test_mixed_extras_requests_admit_in_separate_batches():
    """Text-only and patch_embeds requests sharing a prompt bucket must
    not stack into one prefill call (the VLM request's image would be
    dropped, or the batch build would KeyError)."""
    model, params, cfg = _smoke("internvl2-1b")
    rng = np.random.default_rng(3)
    pe = rng.normal(size=(1, cfg.vision_prefix_len, cfg.d_model)
                    ).astype(np.float32)

    def reqs():
        return [Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=4),
                Request(uid=1, prompt=rng.integers(0, cfg.vocab_size, 7),
                        max_new_tokens=4,
                        extras={"patch_embeds": pe.copy()})]
    got = ServeEngine(model, params, batch_slots=2,
                      max_seq=64).generate(reqs())
    # reference: each request served alone
    for r in got:
        [solo] = ServeEngine(model, params, batch_slots=2,
                             max_seq=64).generate(
            [Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens,
                     extras={k: v.copy() for k, v in r.extras.items()})])
        assert r.generated == solo.generated, r.uid


def test_paged_serves_request_at_max_seq_limit():
    """prompt + max_new == max_seq + 1 is allowed by the dense engine;
    the paged allocator must not demand one block past the table width
    for it (the final sampled token is never cached)."""
    model, params, cfg = _smoke("llama3.2-1b")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 9)
    req = lambda: [Request(uid=0, prompt=prompt, max_new_tokens=24)]
    dense = ServeEngine(model, params, batch_slots=1,
                        max_seq=32).generate(req())
    paged = ServeEngine(model, params, batch_slots=1, max_seq=32,
                        paged=True, page_size=16).generate(req())
    assert dense[0].generated == paged[0].generated
    assert len(paged[0].generated) == 24


def test_prompt_bucket_capped_at_max_seq():
    """A prompt whose power-of-two bucket exceeds max_seq must still
    admit (the bucket caps at the cache's room) and decode exactly."""
    model, params, cfg = _smoke("llama3.2-1b")
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab_size, 20)      # _bucket(20) = 32
    eng = ServeEngine(model, params, batch_slots=1, max_seq=24)
    [r] = eng.generate([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    assert r.generated == _manual_greedy(model, params, prompt, 4,
                                         max_seq=24)


def test_eos_at_first_token_completes_at_prefill():
    """A request whose prefill sample is EOS generates exactly one token
    and frees its slot without a decode chunk."""
    model, params, cfg = _smoke("llama3.2-1b")
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, 6)
    first = _manual_greedy(model, params, prompt, 1)[0]
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64,
                      eos_token=int(first))
    [r] = eng.generate([Request(uid=0, prompt=prompt, max_new_tokens=10)])
    assert r.generated == [first] and r.done


# ---------------------------------------------------------------------------
# compile-variant budget + donation
# ---------------------------------------------------------------------------

def test_compile_variants_bounded_on_skewed_workload():
    """50 skewed requests compile <= log2(max_seq) + n_buckets jit
    variants (decode chunk lengths are powers of two <= max_chunk; prefill
    rows are fixed-width per power-of-two bucket)."""
    model, params, cfg = _smoke("llama3.2-1b")
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(50):
        plen = int(rng.integers(3, 14))
        new = 40 if i % 7 == 1 else int(rng.integers(1, 12))
        reqs.append(Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                       plen),
                            max_new_tokens=new))
    eng = ServeEngine(model, params, batch_slots=4, max_seq=64)
    eng.generate(reqs)
    assert all(r.done for r in reqs)
    stats = eng.compile_stats
    n_buckets = stats["prefill_bucket_variants"]
    budget = int(np.log2(eng.max_seq)) + n_buckets
    assert stats["n_variants"] <= budget, stats
    # and the decode side alone is bounded by the chunk-length lattice
    assert stats["decode_chunk_variants"] <= int(np.log2(eng.max_chunk)) + 1


def test_decode_chunk_donates_cache_buffers():
    """donate_argnums must actually re-use the cache buffers (no silent
    un-donation): the returned cache leaves alias the inputs."""
    model, params, cfg = _smoke("llama3.2-1b")
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64)
    rng = np.random.default_rng(1)
    eng.generate([Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, 8),
                          max_new_tokens=6)])     # warm-up compiles
    eng.reset()
    eng.submit([Request(uid=1, prompt=rng.integers(0, cfg.vocab_size, 8),
                        max_new_tokens=9)])
    eng._admit()
    st = eng.state
    before = {k: st.cache[k].unsafe_buffer_pointer() for k in st.cache}
    out = eng._chunk_fn(4)(eng.params, st.cache, st.tokens, st.pos,
                           st.remaining, eng.rng)
    new_cache = out[2]
    for k, ptr in before.items():
        assert new_cache[k].unsafe_buffer_pointer() == ptr, \
            f"cache leaf {k!r} was silently copied instead of donated"


# ---------------------------------------------------------------------------
# vectorized planner == scalar reference
# ---------------------------------------------------------------------------

def _expand_sequence_reference(table):
    """The pre-vectorization Python double loop, kept as the oracle."""
    order, phases = [], []
    for k in table.kernels:
        if k.phase not in phases:
            phases.append(k.phase)
    for ph in phases:
        idxs = [i for i, k in enumerate(table.kernels) if k.phase == ph]
        max_inv = max(table.kernels[i].invocations for i in idxs)
        for rep in range(max_inv):
            for i in idxs:
                inv = table.kernels[i].invocations
                if (rep * inv) // max_inv != ((rep + 1) * inv) // max_inv:
                    order.append(i)
    return np.asarray(order, dtype=int)


def _random_table(n_kernels=40, seed=0):
    from repro.core import Campaign, get_chip
    from repro.core.power_model import KernelSpec
    rng = np.random.default_rng(seed)
    kernels = [KernelSpec(name=f"k{i}",
                          kind=["gemm", "softmax", "gelu"][i % 3],
                          flops=float(rng.uniform(1e9, 1e12)),
                          hbm_bytes=float(rng.uniform(1e8, 1e10)),
                          invocations=int(rng.integers(1, 9)),
                          phase=["fwd", "bwd"][i % 2])
               for i in range(n_kernels)]
    return Campaign(get_chip("tpu-v5e"), seed=seed, n_reps=3).run(kernels)


def test_expand_sequence_matches_reference():
    table = _random_table()
    got = __import__("repro.core.coalesce",
                     fromlist=["expand_sequence"]).expand_sequence(table)
    ref = _expand_sequence_reference(table)
    assert np.array_equal(got, ref)


def test_batched_dp_matches_scalar_and_times_exact():
    from repro.core.coalesce import _dp_for_lambdas, _dp_times
    table = _random_table(seed=3)
    from repro.core.coalesce import expand_sequence
    seq = expand_sequence(table)
    T, E = table.time[seq], table.energy[seq]
    sl, se = 1e-6, 1e-4
    lams = np.array([0.0, 1.0, 64.0, 1e6, 1e12])
    chs = _dp_for_lambdas(T, E, lams, sl, se)
    ts, es = _dp_times(T, E, lams, sl, se)
    iidx = np.arange(len(seq))
    for li, lam in enumerate(lams):
        # batched row == independent scalar solve
        solo = _dp_for_lambdas(T, E, np.asarray([lam]), sl, se)[0]
        assert np.array_equal(chs[li], solo), lam
        # forward-only realized time/energy == backtracked realizations
        sw = int(np.sum(chs[li][1:] != chs[li][:-1]))
        t_bt = float(T[iidx, chs[li]].sum()) + sw * sl
        e_bt = float(E[iidx, chs[li]].sum()) + sw * se
        assert ts[li] == pytest.approx(t_bt, rel=1e-12), lam
        assert es[li] == pytest.approx(e_bt, rel=1e-12), lam
    # higher lambda never increases realized time (monotone frontier)
    assert np.all(np.diff(ts) <= 1e-12)


def test_splice_accounting_exact_and_feasible():
    """The duality-gap splice repair reports exactly the time/energy of
    the sequence it returns, and never violates the budget."""
    from repro.core.coalesce import _splice_plans
    rng = np.random.default_rng(9)
    n, C = 200, 6
    T = rng.uniform(1e-4, 1e-2, (n, C))
    E = rng.uniform(1e-2, 1.0, (n, C))
    chA = rng.integers(0, C, n).astype(np.int32)   # "aggressive": slow
    chB = np.argmin(T, axis=1).astype(np.int32)    # "conservative": fast
    sl, se = 1e-5, 1e-3
    iidx = np.arange(n)
    budget = float(T[iidx, chB].sum()) * 1.2
    out = _splice_plans(T, E, chA, chB, budget, sl, se)
    assert out is not None
    ch, t, e = out
    sw = int(np.sum(ch[1:] != ch[:-1]))
    assert t == pytest.approx(float(T[iidx, ch].sum()) + sw * sl,
                              rel=1e-12)
    assert e == pytest.approx(float(E[iidx, ch].sum()) + sw * se,
                              rel=1e-12)
    assert t <= budget
    # an impossible budget yields no splice
    assert _splice_plans(T, E, chA, chB, 0.0, sl, se) is None


def test_coalesced_plan_meets_budget_on_large_sequence():
    from repro.core import WastePolicy, get_chip
    from repro.core.coalesce import coalesced_global_plan
    table = _random_table(n_kernels=60, seed=5)
    cp = coalesced_global_plan(table, WastePolicy(0.005),
                               switch_latency_s=1e-6)
    assert cp.time_pct <= 0.5 + 1e-6
    assert cp.energy_pct < 0.0
