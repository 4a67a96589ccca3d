"""Quantized (int8/fp8) paged-KV page pools: write-path scale semantics,
fused-dequant kernel parity, per-family serve parity at a documented
tolerance, 2x slot capacity at identical KV HBM, and the planner's
roofline feedback loop on the quantized workload model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FAMILY_ARCHS
from conftest import make_requests as _requests
from conftest import smoke_model as _smoke
from repro.configs import REGISTRY, smoke_config
from repro.models import build_model
from repro.models.common import kv_qmax, paged_cache_write_quant
from repro.serve import (KV_DTYPES, PagePool, ServeEngine,
                         kv_dtype_bytes, resolve_kv_dtype)
from repro.serve.kv_pages import (PagedBatchState, scale_key,
                                  write_prefill_pages)

# documented parity tolerance of the quantized serve path (claims.md):
# logits within LOGITS_TOL of the bf16 engine, greedy argmax exact
LOGITS_TOL = 5e-2


# ---------------------------------------------------------------------------
# dtype table + accounting primitives
# ---------------------------------------------------------------------------

def test_resolve_kv_dtype_table():
    assert resolve_kv_dtype(None) is None
    assert resolve_kv_dtype("none") is None
    assert resolve_kv_dtype("bf16") is None
    dt, qmax = resolve_kv_dtype("int8")
    assert dt == jnp.int8 and qmax == 127.0
    with pytest.raises(ValueError):
        resolve_kv_dtype("int3")
    if "fp8_e4m3" in KV_DTYPES:              # gated on this JAX build
        dt, qmax = resolve_kv_dtype("fp8_e4m3")
        assert qmax == 448.0 and jnp.dtype(dt).itemsize == 1
    else:
        with pytest.raises(ValueError):
            resolve_kv_dtype("fp8_e4m3")


def test_kv_dtype_bytes_moves_the_roofline():
    assert kv_dtype_bytes(None) == 2
    assert kv_dtype_bytes("bf16") == 2
    assert kv_dtype_bytes(None, dtype_bytes=4) == 4
    assert kv_dtype_bytes("int8") == 1
    assert kv_qmax(jnp.int8) == 127.0


# ---------------------------------------------------------------------------
# quantize-on-write: prefill scatter + per-token decode write
# ---------------------------------------------------------------------------

def test_write_prefill_pages_quantized_roundtrip():
    """Scattered pages dequantize back to the source within half an LSB
    of each page's absmax scale, and each page's scale is its absmax."""
    rng = np.random.default_rng(0)
    L, P, page, KV, D = 2, 7, 4, 2, 8
    N, S = 2, 8                                # 2 rows x 2 pages each
    pool = jnp.zeros((L, P, page, KV, D), jnp.int8)
    scales = jnp.zeros((L, P, KV), jnp.float32)
    sub = jnp.asarray(rng.normal(size=(L, N, S, KV, D)) *
                      rng.uniform(0.1, 30, size=(L, N, 1, KV, 1)),
                      jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 5]], jnp.int32)
    pool, scales = write_prefill_pages(pool, sub, tables, scales=scales,
                                       qmax=127.0)
    blocks = np.asarray(sub).reshape(L, N * 2, page, KV, D)
    flat = np.asarray(tables).reshape(-1)
    got_scale = np.asarray(scales)
    deq = np.asarray(pool, np.float32) \
        * got_scale[:, :, None, :, None]
    for j, pid in enumerate(flat):
        absmax = np.abs(blocks[:, j]).max(axis=(1, 3))     # (L, KV)
        np.testing.assert_allclose(got_scale[:, pid], absmax / 127.0,
                                   rtol=1e-6)
        err = np.abs(deq[:, pid] - blocks[:, j])
        lsb = got_scale[:, pid][:, None, :, None]
        assert (err <= 0.5 * lsb + 1e-7).all()
    # untouched pages (incl. parking page 0) stay zero with zero scale
    for pid in (0, 4, 6):
        assert not np.asarray(pool[:, pid]).any()
        assert not got_scale[:, pid].any()


def test_write_prefill_pages_unquantized_unchanged():
    rng = np.random.default_rng(1)
    pool = jnp.zeros((1, 5, 4, 2, 8), jnp.float32)
    sub = jnp.asarray(rng.normal(size=(1, 1, 4, 2, 8)), jnp.float32)
    out = write_prefill_pages(pool, sub, jnp.asarray([[2]], jnp.int32))
    assert not isinstance(out, tuple)
    np.testing.assert_array_equal(np.asarray(out[:, 2]),
                                  np.asarray(sub[:, 0]))


def test_paged_cache_write_quant_scale_discipline():
    """First write into a page resets the scale (erasing the previous
    tenant); later writes widen it monotonically and re-quantize the
    page's existing entries, so early small tokens survive a late loud
    one to within the final scale's LSB."""
    rng = np.random.default_rng(2)
    P, page, KV, D = 4, 4, 2, 8
    pages = jnp.asarray(rng.integers(-127, 127, (P, page, KV, D)),
                        jnp.int8)             # stale previous tenant
    scales = jnp.asarray(rng.uniform(1, 2, (P, KV)), jnp.float32)
    orig_sc = np.asarray(scales).copy()
    tables = jnp.asarray([[2, 1]], jnp.int32)  # one slot, pages 2 then 1
    toks = rng.normal(size=(page + 1, 1, KV, D)).astype(np.float32)
    toks[2] *= 50.0                            # loud token mid-page
    for t in range(page + 1):                  # fills page 2, opens page 1
        pages, scales = paged_cache_write_quant(
            pages, scales, jnp.asarray(toks[t]), tables,
            jnp.asarray([t], jnp.int32))
    sc = np.asarray(scales)
    deq = np.asarray(pages, np.float32) * sc[:, None, :, None]
    # page 2 scale is the running absmax of its four tokens / qmax
    np.testing.assert_allclose(
        sc[2], np.abs(toks[:page, 0]).max(axis=(0, 2)) / 127.0, rtol=1e-6)
    for t in range(page):                      # all four tokens recovered
        err = np.abs(deq[2, t] - toks[t, 0])
        assert (err <= 0.5 * sc[2][:, None] + 1e-7).all(), t
    # page 1 was reset on first write: stale tenant gone, scale = token's
    np.testing.assert_allclose(
        sc[1], np.maximum(np.abs(toks[page, 0]).max(axis=-1) / 127.0,
                          1e-8), rtol=1e-6)
    err = np.abs(deq[1, 0] - toks[page, 0])
    assert (err <= 0.5 * sc[1][:, None] + 1e-7).all()
    # untouched pages keep their old scale
    np.testing.assert_array_equal(sc[[0, 3]], orig_sc[[0, 3]])


# ---------------------------------------------------------------------------
# paged_flash_decode parameter combos (interpret mode) vs ref oracle
# ---------------------------------------------------------------------------

def _paged_operands(seed=0, B=3, H=4, KV=2, D=32, P=16, page=16, nb=4,
                    quantized=False):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, P))[:B * nb]
                         .reshape(B, nb), jnp.int32)
    kf = rng.normal(size=(P, page, KV, D)).astype(np.float32)
    vf = rng.normal(size=(P, page, KV, D)).astype(np.float32)
    if not quantized:
        return q, jnp.asarray(kf), jnp.asarray(vf), tables, None, None
    ks = np.abs(kf).max(axis=(1, 3)) / 127.0 + 1e-8       # (P, KV)
    vs = np.abs(vf).max(axis=(1, 3)) / 127.0 + 1e-8
    kq = np.clip(np.round(kf / ks[:, None, :, None]), -127, 127)
    vq = np.clip(np.round(vf / vs[:, None, :, None]), -127, 127)
    return (q, jnp.asarray(kq, jnp.int8), jnp.asarray(vq, jnp.int8),
            tables, jnp.asarray(ks), jnp.asarray(vs))


# pos=0 (first decode token, all but one key masked), window straddling a
# page boundary (page=16, window=20 at pos 30 reaches into the prior
# page), softcap, and their combination
_COMBOS = [(0, 0.0, [0, 13, 30]),
           (20, 0.0, [0, 30, 47]),
           (0, 3.0, [0, 13, 30]),
           (12, 2.0, [0, 30, 47])]


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("window,softcap,positions", _COMBOS)
def test_paged_flash_decode_combos_vs_ref(window, softcap, positions,
                                          quantized):
    from repro.kernels.flash_attention import (paged_attention_ref,
                                               paged_flash_decode)
    q, k, v, tables, ks, vs = _paged_operands(quantized=quantized)
    pos = jnp.asarray(positions, jnp.int32)
    ref = paged_attention_ref(q, k, v, tables, pos, window=window,
                              softcap=softcap, k_scales=ks, v_scales=vs)
    got = paged_flash_decode(q, k, v, tables, pos, window=window,
                             softcap=softcap, k_scales=ks, v_scales=vs,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_quantized_ref_matches_fp_within_quant_error():
    """The scale-aware ref path on int8 pools approximates full-precision
    attention to the quantization error, not to machine epsilon — i.e.
    the dequant actually happens (a missing scale would be ~127x off)."""
    from repro.kernels.flash_attention import paged_attention_ref
    q, kq, vq, tables, ks, vs = _paged_operands(seed=5, quantized=True)
    kf = jnp.asarray(np.asarray(kq, np.float32) *
                     np.asarray(ks)[:, None, :, None])
    vf = jnp.asarray(np.asarray(vq, np.float32) *
                     np.asarray(vs)[:, None, :, None])
    pos = jnp.asarray([13, 30, 47], jnp.int32)
    full = paged_attention_ref(q, kf, vf, tables, pos)
    quant = paged_attention_ref(q, kq, vq, tables, pos,
                                k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(quant), np.asarray(full),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# serve parity: logits tolerance + exact greedy argmax, every family
# ---------------------------------------------------------------------------

_HEAVY = [pytest.param("hybrid", marks=pytest.mark.slow),
          pytest.param("encdec", marks=pytest.mark.slow)]


@pytest.mark.parametrize("family", ["transformer", "ssm"] + _HEAVY)
def test_quantized_decode_logits_within_tolerance(family):
    """Single-step an int8 pool and a bf16 pool over the same prompts:
    logits within the documented tolerance at every step."""
    model, params, cfg = _smoke(FAMILY_ARCHS[family])
    reqs = _requests(cfg, n=2)[:2]
    base = ServeEngine(model, params, batch_slots=2, max_seq=64,
                       paged=True, page_size=16)
    quant = ServeEngine(model, params, batch_slots=2, max_seq=64,
                        paged=True, page_size=16, kv_dtype="int8")
    for eng in (base, quant):
        eng.submit([dataclasses.replace(r, generated=[]) for r in reqs])
        eng._admit()
    step = jax.jit(lambda c, t, q, tb: model.decode_step(
        params, c, t, q, block_tables=tb))
    btok, bpos = base.state.tokens, base.state.pos
    bcache, qcache = base.state.cache, quant.state.cache
    qtok, qpos = quant.state.tokens, quant.state.pos
    assert np.array_equal(np.asarray(btok), np.asarray(qtok))
    for i in range(4):
        lb, bcache = step(bcache, btok, bpos, base.state.tables_dev)
        lq, qcache = step(qcache, qtok, qpos, quant.state.tables_dev)
        assert float(jnp.max(jnp.abs(lb - lq))) <= LOGITS_TOL, (family, i)
        # exact greedy agreement: feed the bf16 argmax to both
        btok = qtok = jnp.argmax(lb, -1).astype(jnp.int32)
        assert np.array_equal(np.asarray(jnp.argmax(lb, -1)),
                              np.asarray(jnp.argmax(lq, -1))), (family, i)
        bpos, qpos = bpos + 1, qpos + 1


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(FAMILY_ARCHS.values()))
def test_quantized_engine_greedy_matches_bf16(arch):
    """Full engine runs: identical greedy tokens int8 vs bf16 pools at
    moderate horizons, all four families."""
    model, params, cfg = _smoke(arch)
    base = ServeEngine(model, params, batch_slots=2, max_seq=64,
                       paged=True, page_size=16).generate(_requests(cfg))
    quant = ServeEngine(model, params, batch_slots=2, max_seq=64,
                        paged=True, page_size=16,
                        kv_dtype="int8").generate(_requests(cfg))
    for x, y in zip(base, quant):
        assert x.generated == y.generated, (arch, x.uid)


def test_kv_dtype_requires_paged_engine():
    model, params, cfg = _smoke("llama3.2-1b")
    with pytest.raises(ValueError):
        ServeEngine(model, params, batch_slots=2, max_seq=64,
                    kv_dtype="int8")
    with pytest.raises(ValueError):
        ServeEngine(model, params, batch_slots=2, max_seq=64, paged=True,
                    page_size=16, kv_dtype="int3")


# ---------------------------------------------------------------------------
# capacity: 2x slots at identical KV HBM; peak occupancy; HBM split
# ---------------------------------------------------------------------------

def test_double_slots_at_identical_kv_hbm():
    """An int8 pool with twice the pages of a bf16-width pool costs no
    more attention-KV HBM (payload halves; scale leaves are <2% here)
    while serving 2x the slots — the >=1.8x capacity claim."""
    arch = FAMILY_ARCHS["transformer"]
    cfg = smoke_config(REGISTRY[arch])        # bf16 serving dtype
    model = build_model(cfg, block_k=16)
    slots, max_seq, page = 4, 96, 16
    n_pages = slots * max_seq // page
    base = PagedBatchState(model, slots, max_seq, page_size=page,
                           n_pages=n_pages)
    quant = PagedBatchState(model, 2 * slots, max_seq, page_size=page,
                            n_pages=2 * n_pages, kv_dtype="int8")
    assert base.cache[model.paged_cache_keys()[0]].dtype == jnp.bfloat16
    assert quant.cache[model.paged_cache_keys()[0]].dtype == jnp.int8
    slot_ratio = quant.n_slots / base.n_slots
    hbm_ratio = quant.kv_hbm_bytes() / base.kv_hbm_bytes()
    assert slot_ratio >= 1.8
    assert hbm_ratio <= 1.02          # identical payload + <2% scales
    # scale leaves exist and are charged to the accounting
    k0 = model.paged_cache_keys()[0]
    assert scale_key(k0) in quant.cache
    assert scale_key(k0) not in base.cache


def test_page_pool_peak_allocated_high_water():
    pool = PagePool(n_pages=9, page_size=4, n_slots=3, max_blocks=4)
    assert pool.stats()["peak_allocated_pages"] == 0
    pool.allocate(0, 9)                       # 3 pages
    pool.allocate(1, 8)                       # +2 -> 5
    assert pool.stats()["peak_allocated_pages"] == 5
    pool.free(0)                              # down to 2 ...
    assert pool.stats()["allocated_pages"] == 2
    assert pool.stats()["peak_allocated_pages"] == 5   # ... peak holds
    pool.allocate(2, 16)                      # 2 + 4 = 6: new peak
    assert pool.stats()["peak_allocated_pages"] == 6


def test_sync_tables_skips_when_pool_unchanged():
    """The device mirror only re-uploads after an allocate/free."""
    model, _, _ = _smoke("llama3.2-1b")
    st = PagedBatchState(model, 2, 64, page_size=16)
    st.pool.allocate(0, 20)
    st.sync_tables()
    dev = st.tables_dev
    st.sync_tables()                          # no allocator movement
    assert st.tables_dev is dev               # skipped: same buffer
    st.pool.allocate(1, 8)                    # version bump
    st.sync_tables()
    assert st.tables_dev is not dev
    np.testing.assert_array_equal(np.asarray(st.tables_dev),
                                  st.pool.tables)
    dev = st.tables_dev
    st.pool.free(0)                           # frees also dirty the mirror
    st.sync_tables()
    assert st.tables_dev is not dev


@pytest.mark.parametrize("family", ["ssm", "hybrid"])
def test_kv_vs_cache_hbm_split(family):
    """kv_hbm_bytes counts only the paged attention-KV leaves; dense
    SSM/conv state lives in cache_hbm_bytes."""
    model, params, cfg = _smoke(FAMILY_ARCHS[family])
    eng = ServeEngine(model, params, batch_slots=2, max_seq=64)
    kv = eng.state.kv_hbm_bytes()
    total = eng.state.cache_hbm_bytes()
    if family == "ssm":                       # no attention KV at all
        assert kv == 0 and total > 0
    else:                                     # hybrid: both kinds present
        assert 0 < kv < total
    # paged state draws the same distinction
    ps = PagedBatchState(model, 2, 64, page_size=16)
    if family == "hybrid":
        assert 0 < ps.kv_hbm_bytes() < ps.cache_hbm_bytes()


# ---------------------------------------------------------------------------
# planner roofline feedback: the quantized workload model re-plans deeper
# ---------------------------------------------------------------------------

def test_workload_model_halves_only_the_paged_kv_stream():
    from repro.configs.base import ShapeConfig
    from repro.core.workload import WorkloadBuilder
    cfg = REGISTRY["llama3.2-1b"]
    dec = ShapeConfig(name="d", seq_len=1024, global_batch=4,
                      kind="decode")
    base = {k.name: k for k in WorkloadBuilder(cfg, dec).build()}
    quant = {k.name: k for k in
             WorkloadBuilder(cfg, dec, kv_dtype="int8").build()}
    assert base.keys() == quant.keys()
    for name in base:
        b, q = base[name], quant[name]
        assert b.flops == q.flops, name
        if "Attn cache read" in name:
            assert q.hbm_bytes == b.hbm_bytes / 2, name
        else:
            assert q.hbm_bytes == b.hbm_bytes, name


def test_workload_model_keeps_cross_attention_dense():
    """encdec cross-attention K/V is not paged: its cache-read stream
    must stay at the compute width under a quantized kv_dtype."""
    from repro.configs.base import ShapeConfig
    from repro.core.workload import WorkloadBuilder
    cfg = REGISTRY[FAMILY_ARCHS["encdec"]]
    dec = ShapeConfig(name="d", seq_len=512, global_batch=4, kind="decode")
    base = {k.name: k for k in WorkloadBuilder(cfg, dec).build()}
    quant = {k.name: k for k in
             WorkloadBuilder(cfg, dec, kv_dtype="int8").build()}
    assert quant["Cross cache read"].hbm_bytes \
        == base["Cross cache read"].hbm_bytes
    assert quant["Attn cache read"].hbm_bytes \
        == base["Attn cache read"].hbm_bytes / 2


def test_quantized_replan_lands_deeper_serve_energy_cut():
    """Re-planning the decode phases on the quantized workload model at
    the same tau plans strictly less energy at every bucket: the halved
    cache-read stream shifts the decode roofline (planned base time and
    energy drop), the coalesced clock schedule re-groups, and the serve
    energy cut measured against the shared un-governed bf16 baseline is
    strictly deeper — by several points at the KV-heavy top bucket."""
    from repro.configs.base import ShapeConfig
    from repro.core.objectives import WastePolicy
    from repro.core.power_model import get_chip
    from repro.dvfs import plan_serve_phases
    cfg = REGISTRY["llama3.2-1b"]
    chip = get_chip("tpu-v5e")
    pre = ShapeConfig(name="p", seq_len=256, global_batch=1,
                      kind="prefill")
    dec = ShapeConfig(name="d", seq_len=4096, global_batch=8,
                      kind="decode")
    plans = {}
    for kvd in (None, "int8"):
        plan = plan_serve_phases(cfg, chip, n_slots=8, prefill_shape=pre,
                                 decode_shape=dec,
                                 policy=WastePolicy(0.005), n_reps=2,
                                 kv_dtype=kvd)
        assert plan.meta["kv_dtype"] == (kvd or "none")
        plans[kvd or "bf16"] = plan
    for bucket in (1, 2, 4, 8):
        m0 = plans["bf16"].segment(f"decode@{bucket}").schedule.meta
        m1 = plans["int8"].segment(f"decode@{bucket}").schedule.meta
        # the planner sees the shifted roofline ...
        assert m1["base_time_s"] < m0["base_time_s"], bucket
        assert m1["base_energy_j"] < m0["base_energy_j"], bucket
        # ... and plans strictly less decode energy at the same tau
        gov0 = m0["base_energy_j"] * (1 + m0["energy_pct"] / 100)
        gov1 = m1["base_energy_j"] * (1 + m1["energy_pct"] / 100)
        assert gov1 < gov0, bucket
        assert abs(m1["time_pct"]) <= 0.5 + 1e-6          # tau respected
    # top bucket (most HBM-bound decode): the cut against the shared
    # bf16 baseline deepens by >5 points (quantization + DVFS compound)
    m0 = plans["bf16"].segment("decode@8").schedule.meta
    m1 = plans["int8"].segment("decode@8").schedule.meta
    gov0 = m0["base_energy_j"] * (1 + m0["energy_pct"] / 100)
    gov1 = m1["base_energy_j"] * (1 + m1["energy_pct"] / 100)
    cut0 = 1 - gov0 / m0["base_energy_j"]
    cut1 = 1 - gov1 / m0["base_energy_j"]
    assert cut1 > cut0 + 0.05
    # prefill is untouched by kv_dtype (no decode cache-read stream)
    p0 = plans["bf16"].segment("prefill").schedule.meta
    p1 = plans["int8"].segment("prefill").schedule.meta
    assert p0["base_energy_j"] == p1["base_energy_j"]


def test_session_plan_serve_threads_kv_dtype():
    """DvfsSession.plan_serve(kv_dtype=...) stamps the plan meta and
    plans against the quantized workload model."""
    from repro.configs.base import ShapeConfig
    from repro.dvfs import DvfsSession
    cfg = REGISTRY["llama3.2-1b"]
    pre = ShapeConfig(name="p", seq_len=128, global_batch=1,
                      kind="prefill")
    dec = ShapeConfig(name="d", seq_len=512, global_batch=2, kind="decode")
    with DvfsSession(chip="tpu-v5e", tau=0.005, n_reps=2) as sess:
        plan = sess.plan_serve(cfg, n_slots=2, prefill_shape=pre,
                               decode_shape=dec, kv_dtype="int8")
        assert plan.meta.get("kv_dtype") == "int8"
