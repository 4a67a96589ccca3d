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
import re

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


_KERNEL = re.compile(
    r'op_name="[^"]*?(flash_fwd|flash_bwd_dq|flash_bwd_dkv)\W')


def _traced_op(line, name):
    """The kernel call of the compiled instruction ``line`` rebuilt as a
    profiler trace names it (result and operand shapes with the call)."""
    from bench.trace import Op
    result = re.match(r"\s*%?[\w.\-]+ = (.*?) custom-call\(",
                      line).group(1)
    constraints = line.split("operand_layout_constraints={", 1)[1]
    constraints = re.sub(r"\{[^{}]*\}", "", constraints).split("}")[0]
    operands = re.findall(r"[a-z]+\d*\[[\d,]*\]", constraints)
    return Op(0.0, 1.0, (
        f"%{name}.1 = {result} custom-call({', '.join(operands)}), "
        f'custom_call_target="tpu_custom_call"'))


def _flash_calls(text):
    """``{kernel name: bench.trace.Op}`` for each flash kernel call in the
    compiled text."""
    calls = {}
    for line in text.splitlines():
        name = _KERNEL.search(line)
        if 'custom_call_target="tpu_custom_call"' not in line or not name:
            continue
        calls[name.group(1)] = _traced_op(line, name.group(1))
    return calls


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
    # the benchmark's roofline readers find each kernel by its operand and
    # result shapes: the calls keep their names and shapes
    from bench.kernels import flash_bwd, flash_fwd
    calls = _flash_calls(_compiled_text(fn, *qkv))
    names = ["flash_fwd"] + (["flash_bwd_dq", "flash_bwd_dkv"]
                             if backward else [])
    assert sorted(calls) == sorted(names)
    assert flash_fwd.call_cost(calls["flash_fwd"]) == \
        flash_fwd.cost(B * H, S, D)
    if backward:
        assert flash_bwd.call_cost(calls["flash_bwd_dq"]) == \
            flash_bwd.cost(B * H, S, D)
        assert flash_bwd.call_cost(calls["flash_bwd_dkv"]) == (0.0, 0.0)
        assert flash_fwd.call_cost(calls["flash_bwd_dq"]) is None


@pytest.mark.parametrize("backward", [False, True])
def test_mla_flash_train_compiles(one_chip, backward):
    """The flash kernels at train-moonlight-ep8's shapes: one row of 8192
    tokens, 16 heads, q/k of 192 and v of 128, blocks of 1024 (an 8 x 8
    grid with the causal sub-tile plan).  They keep their three names and
    the benchmark's latent-attention readers find them by their shapes."""
    B, S, H, Dqk, Dv, block = 1, 8192, 16, 192, 128, 1024
    sds = lambda d: jax.ShapeDtypeStruct((B, S, H, d), jnp.bfloat16,
                                         sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention_train(q, k, v, True, 0, block, block, False)

    fn = fwd
    if backward:
        fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    from bench.kernels import flash_fwd, mla_flash_bwd, mla_flash_fwd
    calls = _flash_calls(_compiled_text(fn, sds(Dqk), sds(Dqk), sds(Dv)))
    names = ["flash_fwd"] + (["flash_bwd_dq", "flash_bwd_dkv"]
                             if backward else [])
    assert sorted(calls) == sorted(names)
    assert mla_flash_fwd.call_cost(calls["flash_fwd"]) == \
        mla_flash_fwd.cost(B * H, S, Dqk, Dv)
    if backward:
        assert mla_flash_bwd.call_cost(calls["flash_bwd_dq"]) == \
            mla_flash_bwd.cost(B * H, S, Dqk, Dv)
        assert mla_flash_bwd.call_cost(calls["flash_bwd_dkv"]) == (0.0, 0.0)
        assert mla_flash_fwd.call_cost(calls["flash_bwd_dq"]) is None
        assert flash_fwd.call_cost(calls["flash_bwd_dq"]) is None


@pytest.mark.parametrize("backward", [False, True])
def test_routed_expert_layer_compiles(one_chip, monkeypatch, backward):
    """The expert layer at train-moonlight-ep8's shapes: 8192 tokens of
    2048, top-6 of 64 experts, 8 of them held, of 1408.  Its grouped
    matmuls (megablox ``gmm``, and ``tgmm`` for the weight gradient) run
    over the capped buffer of 12,288 rows and, in the fallback, over all
    49,152 copies; the benchmark's reader recognises every call."""
    from bench.kernels import moe_experts
    from repro.models import common as cm
    monkeypatch.setattr(cm, "_attention_kernels",
                        lambda: {"interpret": False})
    E, n, d, f = 64, 8, 2048, 1408
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    p = {"router": sds((d, E), jnp.float32),
         "select_bias": sds((E,), jnp.float32),
         "w_gate": sds((n, d, f), jnp.float32),
         "w_up": sds((n, d, f), jnp.float32),
         "w_down": sds((n, f, d), jnp.float32)}

    def layer(p, x):
        return cm.apply_routed_moe(p, x, n_experts=E, top_k=6,
                                   routed_scale=2.446)[0]

    fn = layer
    if backward:
        fn = jax.grad(lambda p, x: layer(p, x).astype(jnp.float32).sum(),
                      argnums=(0, 1))
    text = _compiled_text(fn, p, sds((1, 8192, d), jnp.bfloat16))
    rows = []
    for line in text.splitlines():
        kernel = re.match(r"\s*%(t?gmm)\.\d+ = ", line)
        if not kernel or 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = _traced_op(line, kernel.group(1))
        assert moe_experts.call_cost(op, n / E) is not None, line
        rows.append(int(re.search(r"\[(\d+),", op.shape if kernel.group(
            1) == "gmm" else op.operand_shapes[-1]).group(1)))
    # forward: gate, up and down; backward: those again inside the
    # gradient, the two gradients of each input and the three weights'
    per_branch = 9 if backward else 3
    assert sorted(rows) == [12288] * per_branch + [49152] * per_branch


@pytest.mark.parametrize("checkpoint,forwards", [("remat", 1), ("plain", 2)])
def test_remat_mla_stack_runs_flash_forward_once(one_chip, monkeypatch,
                                                 checkpoint, forwards):
    """The gradient of a 2-layer latent-attention stack at Moonlight's
    widths (2048 wide, 16 heads, q/k 192, v 128; S 1024) under the layer
    checkpoint keeps the forward kernel's output and log-sum-exp: its
    backward loop holds the two backward kernels and no second forward,
    which the plain checkpoint's does."""
    from repro.configs.base import MLAConfig
    from repro.models import common as cm
    monkeypatch.setattr(cm, "_attention_kernels",
                        lambda: {"interpret": False})
    mla = MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                    qk_rope_head_dim=64, v_head_dim=128)
    d, H, S = 2048, 16, 1024
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    params = {"wq": sds((2, d, H, 192)), "w_kva": sds((2, d, 512 + 64)),
              "kv_norm": {"scale": sds((2, 512))},
              "w_kvb": sds((2, 512, H, 128 + 128)),
              "wo": sds((2, H, 128, d))}
    wrap = cm.remat if checkpoint == "remat" else \
        (lambda fn: jax.checkpoint(fn, prevent_cse=False))

    def layer(x, p):
        return x + cm.mla_attention_block(p, x, n_heads=H, mla=mla,
                                          theta=5e4, block_k=1024), None

    def loss(params, x):
        y, _ = jax.lax.scan(wrap(layer), x.astype(jnp.bfloat16), params)
        return y.astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss), params, sds((1, S, d)))
    calls = [m.group(1) for m in map(_KERNEL.search, text.splitlines())
             if m and "tpu_custom_call" in m.string]
    assert sorted(calls) == sorted(
        ["flash_fwd"] * forwards + ["flash_bwd_dq", "flash_bwd_dkv"])
