"""``moe_overflow_share.train`` on synthetic traces: the share of the
expert grouped matmuls that ran over the buffer of all T x K copies (49,152
rows at train-moonlight-ep8's 8192 tokens a microbatch and top-6) and not
the capped buffer (12,288 rows)."""
import copy

import pytest

from bench import harness as H
from bench.trace import Device, Op, Trace

CFG = H.load_json(H.BENCH / "configs" / "moonlight-16b-a3b-ep8.json")
TRAFFIC = H.load_json(H.BENCH / "traffic" / "seq8k-batch8.json")
READER = H.load_module(H.BENCH / "metrics" / "moe_overflow_share.train.py")


def gmm(rows):
    """A forward ``gmm`` call over ``rows`` as the trace names it."""
    return (f"%gmm.4 = bf16[{rows},1408]{{1,0}} custom-call(s32[10]{{0}} "
            f"%o, s32[104]{{0}} %i, s32[104]{{0}} %t, s32[1]{{0}} %g, "
            f"bf16[{rows},2048]{{1,0}} %x, bf16[8,2048,1408]{{2,1,0}} %w), "
            f"custom_call_target=\"tpu_custom_call\"")


def tgmm(rows):
    """A weight-gradient ``tgmm`` call over ``rows``."""
    return (f"%tgmm.2 = bf16[8,2048,1408]{{2,1,0}} custom-call(s32[10]{{0}} "
            f"%o, s32[104]{{0}} %i, s32[104]{{0}} %t, s32[1]{{0}} %g, "
            f"bf16[{rows},2048]{{1,0}} %x, bf16[{rows},1408]{{1,0}} %dy), "
            f"custom_call_target=\"tpu_custom_call\"")


class Ctx:
    def __init__(self, texts, config=CFG):
        ops = [Op(10 * i, 10 * i + 5, t) for i, t in enumerate(texts)]
        ops.append(Op(0, 3, "%fusion.1 = bf16[49152,2048] fusion()"))
        d = Device("/device:TPU:0", ops=ops)
        self.trace = Trace([d], [("bench.window", 0, 1e6)], (0, 1e6))
        self.config, self.traffic = config, TRAFFIC


def test_every_call_capped_reads_zero():
    assert READER.read(Ctx([gmm(12288)] * 3 + [tgmm(12288)] * 3)) == 0.0


def test_mixed_calls_read_their_share():
    texts = [gmm(12288)] * 6 + [tgmm(12288)] * 3 + [gmm(49152)] * 2 + \
        [tgmm(49152)]
    assert READER.read(Ctx(texts)) == pytest.approx(100.0 * 3 / 12)


def test_no_cap_reads_every_call():
    assert READER.read(Ctx([gmm(49152), tgmm(49152)])) == 100.0


def test_no_routed_experts_reads_none():
    dense = copy.deepcopy(CFG)
    del dense["model"]["moe"]
    assert READER.read(Ctx([gmm(12288)], dense)) is None
    assert READER.read(Ctx([])) is None
