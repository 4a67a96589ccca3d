"""The readers that train-moonlight-ep8 adds: the latent-attention flash
kernels' and the expert grouped matmuls' counts on instruction texts as a
TPU trace names them, the model's operations a token, and device time by
the expert layer's sub-scopes (``bench/moe_scopes.py``) on synthetic
events and on the step compiled for a small cell on the CPU."""
import copy

import jax
import pytest

from bench import harness as H, moe_flops, moe_scopes, scopes
from bench.trace import Device, Op, Trace

T = "{2,1,0:T(8,128)(2,1)}"
MLA_FWD = (f"%closed_call.3 = (bf16[16,8192,128]{T}, f32[16,8192,1]{{2,1,0}})"
           f" custom-call(bf16[16,8192,192]{T} %a, bf16[16,8192,192]{T} %b, "
           f"bf16[16,8192,128]{T} %c), custom_call_target=\"tpu_custom_call\"")
SIX = (f"custom-call(bf16[16,8192,192]{T} %q, bf16[16,8192,192]{T} %k, "
       f"bf16[16,8192,128]{T} %v, bf16[16,8192,128]{T} %o, "
       f"bf16[16,8192,128]{T} %do, f32[16,8192,1]{{2,1,0}} %lse), "
       f"custom_call_target=\"tpu_custom_call\"")
MLA_DQ = f"%checkpoint.8 = bf16[16,8192,192]{T} " + SIX
MLA_DKV = (f"%checkpoint.9 = (bf16[16,8192,192]{T}, bf16[16,8192,128]{T}) "
           + SIX)
FWD128 = (f"%closed_call.7 = (bf16[128,1024,128]{T}, f32[128,1024,1]"
          f"{{2,1,0}}) custom-call(bf16[128,1024,128]{T} %a, "
          f"bf16[128,1024,128]{T} %b, bf16[128,1024,128]{T} %c), "
          f"custom_call_target=\"tpu_custom_call\"")
SIX128 = SIX.replace("8192,192", "1024,128").replace("16,", "128,")
DQ128 = f"%checkpoint.18 = bf16[128,1024,128]{T} " + SIX128
META = ("%ragged-dot-metadata = (s32[9]{0}, s32[103]{0}, s32[103]{0}, "
        "s32[1]{0}) custom-call(s32[8]{0} %gs), "
        "custom_call_target=\"tpu_custom_call\"")
RAGGED = ("%ragged-dot-none.3 = bf16[49152,1408]{1,0} custom-call(s32[1]{0} "
          "%g, s32[9]{0} %a, s32[103]{0} %b, s32[103]{0} %c, s32[1]{0} %d, "
          "bf16[49152,2048]{1,0} %x, bf16[8,2048,1408]{2,1,0} %w), "
          "custom_call_target=\"tpu_custom_call\"")
GMM = ("%jit_gmm_.4 = bf16[49152,1408]{1,0} custom-call(s32[10]{0} %o, "
       "s32[104]{0} %i, s32[104]{0} %t, s32[1]{0} %g, bf16[49152,2048]{1,0} "
       "%x, bf16[8,2048,1408]{2,1,0} %w), "
       "custom_call_target=\"tpu_custom_call\"")
RAGGED_W = ("%ragged-dot-tr.5 = f32[8,2048,1408]{2,1,0} custom-call(s32[1]"
            "{0} %g, bf16[49152,2048]{1,0} %x, bf16[49152,1408]{1,0} %dy), "
            "custom_call_target=\"tpu_custom_call\"")


def op(text):
    return Op(0.0, 1e6, text)


def test_mla_flash_fwd_counts():
    k = H.kernel("mla_flash_fwd")
    flops, nbytes = k.cost(2, 4, 8, 4)
    # 10 causal pairs: q.k 8 wide and p.v 4 wide, 2 operations a product
    assert flops == 2 * 10 * (8 + 4) * 2
    # q, k (8) read, v (4) read and o (4) written in bf16, lse in f32
    assert nbytes == 2 * 2 * 4 * (8 + 8 + 4 + 4) + 2 * 4 * 4
    assert k.call_cost(op(MLA_FWD)) == k.cost(16, 8192, 192, 128)
    for other in (MLA_DQ, MLA_DKV, FWD128, DQ128, RAGGED):
        assert k.call_cost(op(other)) is None


def test_mla_flash_bwd_counts():
    k = H.kernel("mla_flash_bwd")
    flops, nbytes = k.cost(2, 4, 8, 4)
    # scores, dq, dk over 8; dp, dv over 4
    assert flops == 2 * 10 * (3 * 8 + 2 * 4) * 2
    # q, k, dq, dk (8) and v, o, do, dv (4) in bf16, lse in f32
    assert nbytes == 2 * 2 * 4 * (4 * 8 + 4 * 4) + 2 * 4 * 4
    assert k.call_cost(op(MLA_DQ)) == k.cost(16, 8192, 192, 128)
    assert k.call_cost(op(MLA_DKV)) == (0.0, 0.0)
    for other in (MLA_FWD, FWD128, DQ128, RAGGED):
        assert k.call_cost(op(other)) is None


def test_expert_grouped_matmul_counts():
    """The grouped matmuls by their arrays: forward (rows, weights, out)
    and weight gradient (rows, output gradient, weights)."""
    k = H.kernel("moe_experts")
    # counted at an eighth of the 49152 rows: 6144
    flops, nbytes = k.call_cost(op(RAGGED), 1 / 8)
    assert flops == 2 * 6144 * 2048 * 1408
    assert nbytes == 2 * (6144 * 2048 + 6144 * 1408 + 8 * 2048 * 1408)
    assert k.call_cost(op(GMM), 1 / 8) == (flops, nbytes)
    flops, nbytes = k.call_cost(op(RAGGED_W), 1 / 8)
    assert flops == 2 * 6144 * 2048 * 1408
    assert nbytes == 2 * 6144 * (2048 + 1408) + 4 * 8 * 2048 * 1408
    for other in (META, MLA_FWD, MLA_DQ, MLA_DKV, FWD128, DQ128):
        assert k.call_cost(op(other), 1 / 8) is None


def test_model_operations_a_token():
    m = H.load_json(H.BENCH / "configs" / "moonlight-16b-a3b-ep8.json")[
        "model"]
    d, H_ = 2048, 16
    attn_w = d * H_ * 192 + d * 576 + 512 * H_ * 256 + H_ * 128 * d
    expert = 3 * d * 1408
    weights = (5 * attn_w + 3 * d * 11264
               + 4 * (d * 64 + expert * (6 * 8 / 64 + 2)) + d * 20480)
    attn = 3 * 5 * 2 * H_ * (192 + 128) * 8193 / 2
    assert moe_flops.flops_per_token(m, 8192) == pytest.approx(
        6 * weights + attn)
    assert moe_flops.flops_per_token(m, 8192) == pytest.approx(2.283e9,
                                                               rel=1e-3)


@pytest.mark.parametrize("op_name, sub", [
    ("jit(train_step)/while/body/checkpoint/mlp/router/logistic", "router"),
    ("jit(train_step)/transpose(jvp(mlp))/dispatch/gather", "dispatch"),
    ("jit(train_step)/mlp/experts/ragged_dot;mlp/experts/ragged_dot",
     "experts"),
    ("jit(train_step)/mlp/shared/mlp/bsd,df->bsf/dot_general", "shared"),
    ("jit(train_step)/mlp/bsd,df->bsf/dot_general", None),
    ("jit(train_step)/attn/dot_general", None),
])
def test_moe_scope_of(op_name, sub):
    assert moe_scopes.moe_scope_of(op_name) == sub


HLO = """HloModule jit_s, is_scheduled=true

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop, \
metadata={op_name="jit(s)/mlp/experts/ragged_dot"}
  %fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop, \
metadata={op_name="jit(s)/mlp/router/logistic"}
  ROOT %copy.3 = bf16[8]{0} copy(bf16[8]{0} %fusion.2), \
metadata={op_name="jit(s)/mlp/add"}
}
""".replace("\\\n", "")


class Ctx:
    def __init__(self, trace):
        self.trace = trace
        self.counts = {"traced_steps": 2}
        self.config = {"kind": "train", "train": {"mesh": None}}
        self.traffic = {}


def test_readers_on_synthetic_events(monkeypatch):
    d = Device("/device:TPU:0",
               ops=[Op(0, 30, "%fusion.1 = bf16[8] fusion()"),
                    Op(30, 40, "%fusion.2 = bf16[8] fusion()"),
                    Op(40, 50, "%copy.3 = bf16[8] copy()")],
               modules=[Op(0, 50, "jit_s(1)")])
    tr = Trace([d], [("bench.window", 0, 50)], (0, 50))
    assert moe_scopes.seconds_by_moe_scope(tr, HLO) == {
        "experts": pytest.approx(30e-9), "router": pytest.approx(10e-9),
        None: pytest.approx(10e-9)}
    monkeypatch.setattr(scopes, "program_hlo", lambda ctx: HLO)
    ctx = Ctx(tr)
    ms = {s: H.load_module(H.BENCH / "metrics" / f"moe_{s}_ms.train.py"
                           ).read(ctx) for s in moe_scopes.MOE_SCOPES}
    assert ms == {"experts": pytest.approx(15e-6),
                  "router": pytest.approx(5e-6),
                  "dispatch": None, "shared": None}


def test_compiled_step_carries_the_four_sub_scopes():
    """The step of a small moonlight cell compiled on the CPU: its
    instructions carry each sub-scope of the expert layer."""
    H.import_program()
    config = copy.deepcopy(H.load_json(
        H.BENCH / "configs" / "moonlight-16b-a3b-ep8.json"))
    config["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                           d_ff=96, vocab_size=256, head_dim=24)
    config["model"]["mla"] = {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                              "qk_rope_head_dim": 8, "v_head_dim": 16}
    config["model"]["moe"].update(n_experts=8, experts_held=4, top_k=2,
                                  d_expert=32)
    config["train"].update(accum_steps=2)
    traffic = {"global_batch": 4, "seq_len": 32}
    module, names = scopes.op_names(
        scopes.compiled_step(config, traffic, jax.devices()[:1]))
    assert module == "jit_train_step"
    assert {moe_scopes.moe_scope_of(n) for n in names.values()} == \
        set(moe_scopes.MOE_SCOPES) | {None}


def test_bench_reads_the_program_sub_scopes():
    H.import_program()
    from repro.obs import MOE_SCOPES
    assert moe_scopes.MOE_SCOPES == MOE_SCOPES
