"""The trace reduction: busy union, idle share, per-kernel time and
exposed collectives, on synthetic events and on small traces recorded on
a TPU v5e by ``record_trace.py``."""
from pathlib import Path

import pytest

from bench import trace as TR
from bench.trace import Device, Op, Trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_subtract():
    u = TR.union([(5, 7), (0, 2), (1, 3), (6, 9), (9, 10), (4, 4)])
    assert u == [(0, 3), (5, 10)]
    assert TR.length(u) == 8
    assert TR.subtract(u, [(1, 2), (6, 12)]) == [(0, 1), (2, 3), (5, 6)]
    assert TR.subtract([(0, 10)], []) == [(0, 10)]


def test_leaves_drop_containers():
    loop = Op(0, 100, "%while.1 = s32[] while(s32[] %a)")
    a = Op(10, 20, "%fusion.1 = f32[] fusion()")
    b = Op(30, 90, "%fusion.2 = f32[] fusion()")
    c = Op(120, 130, "%copy.3 = f32[] copy()")
    assert [o.text for o in TR.leaves([c, b, loop, a])] == \
        [a.text, b.text, c.text]


def synthetic():
    """Two chips; chip 0 computes 0-40 and 60-80 ns with an all-reduce in
    flight over 30-70 ns; chip 1 computes 0-50 ns."""
    mm = "%fusion.1 = bf16[8] fusion(bf16[8] %x), kind=kOutput"
    ar = "%all-reduce.2 = f32[8] all-reduce(f32[8] %y)"
    d0 = Device("/device:TPU:0",
                ops=[Op(0, 40, mm), Op(60, 80, mm), Op(30, 70, ar)],
                modules=[Op(0, 80, "jit_train_step(123)")])
    d1 = Device("/device:TPU:1", ops=[Op(0, 50, mm)],
                modules=[Op(0, 50, "jit_train_step(123)")])
    spans = [("bench.window", 0, 100), ("bench.train_step", 0, 5),
             ("bench.host_sync", 75, 100)]
    return Trace([d0, d1], spans, (0, 100))


def test_synthetic_reductions():
    tr = synthetic()
    d0, d1 = tr.devices
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s(d0) == pytest.approx(80e-9)
    assert tr.busy_s(d1) == pytest.approx(50e-9)
    assert tr.idle_share() == pytest.approx(1 - 65 / 100)
    assert tr.collective_s(d0) == (pytest.approx(40e-9), pytest.approx(20e-9))
    assert tr.collective_s(d1) == (0.0, 0.0)
    took, n = tr.op_seconds(d0, lambda o: o.base == "fusion")
    assert (took, n) == (pytest.approx(60e-9), 2)
    took, n = tr.module_seconds(d0, lambda name: name.startswith("jit_train"))
    assert (took, n) == (pytest.approx(80e-9), 1)
    assert tr.idle_gaps() == [["bench.host_sync", pytest.approx(20e-9)]]
    top = tr.device_ops()
    assert top[0] == ["fusion bf16[8]", pytest.approx(55e-9)]


def test_window_clips():
    tr = synthetic()
    tr.window = (10, 50)
    d0 = tr.devices[0]
    assert tr.busy_s(d0) == pytest.approx(40e-9)
    assert tr.collective_s(d0) == (pytest.approx(20e-9), pytest.approx(10e-9))


def recorded(name):
    path = DATA / name
    if not path.exists():
        pytest.fail(f"missing recorded trace {path}")
    return Trace.from_file(str(path))


def test_recorded_one_chip():
    tr = recorded("trace_1chip.xplane.pb")
    assert len(tr.devices) == 1
    dev = tr.devices[0]
    steps = [s for s in tr.spans if s[0] == "bench.step"]
    assert len(steps) == 3
    # three runs of the jitted step, each inside its host span
    took, n = tr.module_seconds(dev, lambda m: m.startswith("jit_step"))
    assert n == 3 and took > 0
    busy = tr.busy_s(dev)
    assert 0 < busy <= took + 1e-9 <= tr.window_s
    # the host waits of 3 ms leave the chip idle for at least that long
    assert 0 < tr.idle_share() < 1
    gaps = tr.idle_gaps()
    assert gaps[0][1] >= 0.003
    assert gaps[0][0] in ("bench.host_wait", "bench.step")
    assert sum(s for _, s in tr.device_ops()) <= busy + 1e-9
