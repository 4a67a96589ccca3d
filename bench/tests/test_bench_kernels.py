"""Kernel operation and byte counts against hand-computed values, and the
recognition of each kernel's call in instruction texts as a TPU trace
names them."""
import pytest

from bench import harness as H
from bench.trace import Op

# instruction texts as the profiler names them on a TPU v5e (gpt3-xl,
# microbatch 8: 128 = 8 rows x 16 heads), shortened after the operands;
# PAGED is the serving path's page-read kernel, which no flash count may
# take for its own
FWD = ("%closed_call.7 = (bf16[128,1024,128]{2,1,0:T(8,128)(2,1)}, "
       "f32[128,1024,1]{2,1,0:T(8,128)}) custom-call(bf16[128,1024,128]"
       "{2,1,0:T(8,128)(2,1)} %bitcast.747, bf16[128,1024,128]{2,1,0:T(8,"
       "128)(2,1)} %bitcast.746, bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)}"
       " %bitcast.745), custom_call_target=\"tpu_custom_call\"")
DQ = ("%checkpoint.18 = bf16[128,1024,128]{2,1,0:T(8,128)(2,1)} custom-call("
      "bf16[128,1024,128]{2,1,0:T(8,128)(2,1)} %bitcast.733, bf16[128,1024,"
      "128]{2,1,0:T(8,128)(2,1)} %bitcast.736, bf16[128,1024,128]{2,1,0:T(8,"
      "128)(2,1)} %bitcast.739, bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)} "
      "%custom-call.51, bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)} "
      "%bitcast.749, f32[128,1024,1]{2,1,0:T(8,128)} %pallas_call.40), "
      "custom_call_target=\"tpu_custom_call\"")
DKV = DQ.replace("%checkpoint.18 = bf16[128,1024,128]{2,1,0:T(8,128)(2,1)}",
                 "%checkpoint.19 = (bf16[128,1024,128]{2,1,0:T(8,128)(2,1)},"
                 " bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)})")
PAGED = ("%closed_call.15 = bf16[8,16,128]{2,1,0:T(8,128)(2,1)S(1)} "
         "custom-call(s32[8,128]{1,0:T(8,128)S(1)} %get-tuple-element.1389, "
         "s32[8]{0:T(128)S(1)} %get-tuple-element.1388, s32[16,256]{1,0:T(8,"
         "128)S(1)} %copy-done.24, bf16[8,16,128]{2,1,0:T(8,128)(2,1)S(1)} "
         "%copy.37, bf16[641,16,16,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "%fusion.190, bf16[641,16,16,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "%fusion.192), custom_call_target=\"tpu_custom_call\"")
FUSION = ("%fusion.556 = bf16[8,1024,8192]{2,1,0:T(8,128)(2,1)} fusion("
          "bf16[8,1024,2048]{2,1,0:T(8,128)(2,1)} %a, bf16[2048,8192]{1,0} "
          "%b), kind=kOutput, calls=%fused_computation.1")


def op(text):
    return Op(0.0, 1e6, text)


def test_flash_fwd_counts():
    k = H.kernel("flash_fwd")
    flops, nbytes = k.cost(2, 4, 8)
    # 10 causal pairs, q.k and p.v, 2 operations a product, 8 wide
    assert flops == 2 * 10 * 8 * 2 * 2
    # q, k, v read and o written in bf16, lse written in f32
    assert nbytes == 4 * 2 * 4 * 8 * 2 + 2 * 4 * 4
    assert k.call_cost(op(FWD)) == k.cost(128, 1024, 128)
    for other in (DQ, DKV, PAGED, FUSION):
        assert k.call_cost(op(other)) is None


def test_flash_bwd_counts():
    k = H.kernel("flash_bwd")
    flops, nbytes = k.cost(2, 4, 8)
    assert flops == 5 * (2 * 10 * 8 * 2)        # five products
    assert nbytes == 8 * 2 * 4 * 8 * 2 + 2 * 4 * 4
    assert k.call_cost(op(DQ)) == k.cost(128, 1024, 128)
    assert k.call_cost(op(DKV)) == (0.0, 0.0)
    for other in (FWD, PAGED, FUSION):
        assert k.call_cost(op(other)) is None


def test_op_parts():
    o = op(PAGED)
    assert o.base == "closed_call"
    assert o.shape == "bf16[8,16,128]"
    assert o.opcode == "custom-call"
    assert o.operand_shapes[:2] == ["s32[8,128]", "s32[8]"]
    assert o.label == "closed_call tpu_custom_call bf16[8,16,128]"
    assert not o.is_collective
    ar = op("%all-reduce.3 = f32[] all-reduce(f32[] %x), replica_groups={}")
    assert ar.is_collective


@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd"])
def test_roofline_share_is_need_over_time(name):
    k = H.kernel(name)
    text = FWD if name == "flash_fwd" else DQ
    f, b = k.cost(128, 1024, 128)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    need = max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])

    class Tr:
        devices = [None]

        @staticmethod
        def ops(dev):
            return [Op(0.0, 2 * need * 1e9, text)]

    ctx = type("Ctx", (), {"trace": Tr, "peaks": peaks})()
    assert H.kernel_roofline(ctx, name) == pytest.approx(50.0)
