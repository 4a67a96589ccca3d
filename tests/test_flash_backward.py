"""Flash-attention backward Pallas kernels vs jax.grad of the oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.backward import flash_attention_bwd
from repro.kernels.flash_attention.kernel import (causal_subtiles,
                                                  flash_attention_fwd)
from repro.kernels.flash_attention.ops import flash_attention_train
from repro.kernels.flash_attention.ref import attention_ref

pytestmark = pytest.mark.slow

RNG = np.random.default_rng(7)


def _grads(B, Sq, Sk, H, KV, D, causal, window=0):
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Sk, KV, D)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), jnp.float32)

    def loss_kernel(q, k, v):
        o = flash_attention_train(q, k, v, causal, window, 16, 16, True)
        return jnp.sum(o * w)

    def loss_ref(q, k, v):
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
        kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
        vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
        o = attention_ref(qf, kf, vf, causal=causal, window=window,
                          group=H // KV)
        return jnp.sum(o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3) * w)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    return gk, gr


@pytest.mark.parametrize("B,Sq,Sk,H,KV,D,causal", [
    (1, 32, 32, 2, 2, 16, True),
    (2, 48, 48, 4, 2, 16, True),     # GQA: dk/dv summed over groups
    (1, 40, 56, 2, 1, 16, False),    # padding both sides
])
def test_flash_bwd_matches_autodiff(B, Sq, Sk, H, KV, D, causal):
    gk, gr = _grads(B, Sq, Sk, H, KV, D, causal)
    for name, a, b in zip(("dq", "dk", "dv"), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_flash_bwd_window():
    gk, gr = _grads(1, 64, 64, 2, 2, 16, True, window=24)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_fwd_value_consistent_with_train_variant():
    B, S, H, D = 1, 32, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
    from repro.kernels.flash_attention import flash_attention
    o1 = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                         interpret=True)
    o2 = flash_attention_train(q, k, v, True, 0, 16, 16, True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("S,H,KV,block,subtile,causal,window,computed", [
    (64, 2, 2, 64, 16, True, 0, 10),     # one diagonal tile, 10 of 16
    (64, 2, 2, 16, 8, True, 0, 3),       # 4x4 grid, 6 tiles skipped
    (56, 2, 2, 16, 8, True, 0, None),    # padded keys: masked tiles
    (64, 2, 2, 16, 8, True, 24, None),   # window: masked tiles
    (64, 2, 2, 64, 16, False, 0, None),  # non-causal: the whole square
    (64, 4, 2, 32, 16, True, 0, 3),      # GQA, 2x2 grid
])
def test_flash_kernels_skip_masked_work(S, H, KV, block, subtile, causal,
                                        window, computed):
    """The forward and both backward kernels, called with a sub-tile edge
    of their own, against ``jax.vjp`` of the float32 oracle."""
    B, D = 2, 16
    G, Sp = H // KV, -(-S // block) * block
    pairs, _ = causal_subtiles(block, block, subtile, causal, window,
                               Sp > S)
    assert (None if pairs is None else len(pairs)) == computed
    q, k, v, do = (jnp.asarray(RNG.normal(size=(n, S, D)), jnp.float32)
                   for n in (B * H, B * KV, B * KV, B * H))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0)))
    rep = lambda x: jnp.repeat(pad(x), G, axis=0)
    o, lse = flash_attention_fwd(
        pad(q), pad(k), pad(v), causal=causal, window=window,
        block_q=block, block_k=block, group=G, kv_len=S, return_lse=True,
        interpret=True, _subtile=subtile)
    grads = flash_attention_bwd(
        pad(q), rep(k), rep(v), o, pad(do), lse, causal=causal,
        window=window, block_q=block, block_k=block, kv_len=S,
        interpret=True, _subtile=subtile)
    dk, dv = (g[:, :S].reshape(B * KV, G, S, D).sum(1) for g in grads[1:])

    o_ref, vjp = jax.vjp(lambda q, k, v: attention_ref(
        q, k, v, causal=causal, window=window, group=G), q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"),
                          (o[:, :S], grads[0][:, :S], dk, dv),
                          (o_ref, *vjp(do))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
