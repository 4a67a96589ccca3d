"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one kernel for a described (not attached) v5e chip and
compiles it with the TPU compiler, which refuses what interpret mode
accepts: blocks not aligned to the tiling, too much VMEM.  Shapes are the
serve path's llama3.2-1b decode (8 slots, 32/8 heads of 64, 16-token
pages, 64 blocks) and the train path's gpt3-xl attention (seq 1024,
16 heads of 128).

The topology is described only inside the module fixture: one process at
a time may load the TPU library, so it must not happen at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (flash_attention_train,
                                           paged_flash_decode)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_flash_decode_compiles(one_chip, kv_dtype):
    B, H, KV, D, page, nb = 8, 32, 8, 64, 16, 64
    P = B * nb + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pages = sds((P, page, KV, D), jnp.dtype(kv_dtype))
    args = [sds((B, 1, H, D), jnp.bfloat16), pages, pages,
            sds((B, nb), jnp.int32), sds((B,), jnp.int32)]
    if kv_dtype == "int8":
        args += [sds((P, KV), jnp.float32)] * 2
        fn = lambda q, k, v, t, p, ks, vs: paged_flash_decode(
            q, k, v, t, p, k_scales=ks, v_scales=vs)
    else:
        fn = lambda q, k, v, t, p: paged_flash_decode(q, k, v, t, p)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("backward", [False, True])
def test_flash_attention_train_compiles(one_chip, backward):
    B, S, H, D, block = 4, 1024, 16, 128, 1024
    qkv = [jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                                sharding=one_chip)] * 3

    def fwd(q, k, v):
        return flash_attention_train(q, k, v, True, 0, block, block, False)

    fn = fwd
    if backward:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    assert "tpu_custom_call" in _compiled_text(fn, *qkv)
