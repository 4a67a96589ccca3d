"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, in interpret mode (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.flash_attention.kernel import (SUBTILE, SUBTILE_COUNTS,
                                                  causal_subtiles,
                                                  flash_attention_fwd)
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.ssd_scan import ssd, ssd_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("B,Sq,Sk,H,KV,D", [
    (2, 64, 64, 4, 2, 16),
    (1, 48, 48, 4, 4, 16),
    (2, 32, 64, 4, 1, 32),
    (1, 96, 96, 8, 2, 8),
    (1, 64, 32, 2, 2, 16),   # more query tiles than key tiles
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, H, KV, D, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), dtype)
    o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                        interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    r = attention_ref(qf, kf, vf, causal=causal, group=H // KV) \
        .reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), **_tol(dtype))


def test_flash_attention_window():
    B, S, H, D = 1, 64, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
    o = flash_attention(q, k, v, causal=True, window=24, block_q=16,
                        block_k=16, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    r = attention_ref(qf, kf, vf, causal=True, window=24) \
        .reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_softcap_and_padding():
    B, Sq, Sk, H, D = 1, 40, 56, 2, 16   # non-multiples of the block size
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Sk, H, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Sk, H, D)), jnp.float32)
    o = flash_attention(q, k, v, causal=False, softcap=20.0, block_q=16,
                        block_k=16, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    r = attention_ref(qf, kf, vf, causal=False, softcap=20.0) \
        .reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("block,c,causal,window,padded,computed,total", [
    (1024, 256, True, 0, False, 10, 16),
    (1024, 128, True, 0, False, 36, 64),
    (1024, 512, True, 0, False, 3, 4),
    (128, 256, True, 0, False, 1, 1),      # c capped at the block
    (1024, 256, False, 0, False, None, 16),  # non-causal: every sub-tile
    (1024, 256, True, 512, False, None, 16),  # window: masked tiles
    (1024, 256, True, 0, True, None, 16),   # padded keys: masked tiles
    (384, 256, True, 0, False, None, 1),    # c does not divide the block
])
def test_causal_subtiles(block, c, causal, window, padded, computed, total):
    pairs, n = causal_subtiles(block, block, c, causal, window, padded)
    assert n == total
    assert (None if pairs is None else len(pairs)) == computed
    if pairs is not None:   # the lower triangle, row by row
        assert list(pairs) == sorted(pairs)
        assert all(j <= i for i, j in pairs)


def test_causal_subtiles_needs_square_blocks():
    assert causal_subtiles(1024, 512, 256, True, 0) == (None, 16)


def test_subtiles_counted_per_lowering():
    """Each trace of the forward adds its plan to the registry, so the
    share of the square computed is known without running anything."""
    def counts():
        snap = SUBTILE_COUNTS.snapshot()
        return [snap.get(f"flash_subtiles_{k}{{kernel=flash_fwd}}",
                         {"value": 0.0})["value"]
                for k in ("computed", "total")]

    before = counts()
    x = jax.ShapeDtypeStruct((2, 1024, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention_fwd(
        q, k, v, block_q=1024, block_k=1024, interpret=True), x, x, x)
    after = counts()
    pairs, total = causal_subtiles(1024, 1024, SUBTILE, True, 0)
    assert [a - b for a, b in zip(after, before)] == [len(pairs), total]


@pytest.mark.parametrize("shape", [(4, 32), (3, 17, 32), (2, 5, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jnp.asarray(RNG.normal(size=shape), dtype)
    w = jnp.asarray(RNG.normal(size=shape[-1:]), dtype)
    o = rmsnorm(x, w, block_rows=8, interpret=True)
    r = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("S,chunk", [(32, 8), (40, 8), (16, 16), (24, 32)])
@pytest.mark.parametrize("G", [1, 2])
def test_ssd_sweep(S, chunk, G):
    B, H, P, N = 2, 4, 8, 16
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.normal(size=(B, S, H))) * 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.float32)
    y, hf = ssd(x, a, Bm, Cm, chunk=chunk, interpret=True)
    Bh = jnp.repeat(Bm, H // G, axis=2)
    Ch = jnp.repeat(Cm, H // G, axis=2)
    yr, hr = ssd_ref(x, a, Bh, Ch)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-4,
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=3e-4,
                               atol=3e-4)


def test_ssd_bf16():
    B, S, H, P, N, G = 1, 16, 2, 8, 8, 1
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.bfloat16)
    a = jnp.asarray(-np.abs(RNG.normal(size=(B, S, H))) * 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.bfloat16)
    Cm = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.bfloat16)
    y, _ = ssd(x, a, Bm, Cm, chunk=8, interpret=True)
    yr, _ = ssd_ref(x, a, Bm.astype(jnp.float32).repeat(H // G, 2),
                    Cm.astype(jnp.float32).repeat(H // G, 2))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=5e-2,
                               atol=5e-2)
