"""Device time by named scope (``bench/scopes.py``) and its readers, on
synthetic events, on a small trace recorded on a TPU v5e with the HLO of
its program (``record_scoped_trace.py``), and on the step compiled for a
small training cell on the CPU."""
from pathlib import Path

import jax
import pytest

from bench import harness as H, scopes
from bench.trace import Device, Op, Trace

DATA = Path(__file__).resolve().parent / "data"
READERS = ["attn_ms.train", "mlp_ms.train", "norm_ms.train",
           "head_ms.train", "grad_accum_ms.train", "opt_ms.train",
           "embed_ms.train", "unscoped_share.train"]

# two programs: in jit_a an mlp product, an opt update and a copy of no
# scope; jit_b reuses the instruction names
HLO_A = """HloModule jit_a, is_scheduled=true, entry_computation_layout={()}

%fused_computation.1 (param_0: bf16[8]) -> bf16[8] {
  %param_0 = bf16[8]{0} parameter(0)
  ROOT %tanh.1 = bf16[8]{0} tanh(bf16[8]{0} %param_0), \
metadata={op_type="tanh" op_name="jit(a)/mlp/tanh" source_file="a.py"}
}

ENTRY %main (x: bf16[8]) -> f32[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop, \
calls=%fused_computation.1, metadata={op_name="jit(a)/mlp/tanh" \
source_file="a.py" source_line=3}
  %fusion.2 = f32[8]{0} fusion(bf16[8]{0} %fusion.1), kind=kLoop, \
calls=%fused_computation.2, metadata={op_name="jit(a)/opt/add"}
  ROOT %copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.2), \
metadata={op_name="jit(a)/copy"}
}
""".replace("\\\n", "")


def reader(name):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py")


class Ctx:
    def __init__(self, trace, traced_steps=2):
        self.trace = trace
        self.counts = {"traced_steps": traced_steps}
        self.config = {"kind": "train", "train": {"mesh": None}}
        self.traffic = {}


@pytest.fixture
def hlo_is(monkeypatch):
    """Give the readers ``text`` as the program's HLO; returns the list of
    the calls made for it."""
    calls = []

    def use(text):
        def program_hlo(ctx):
            calls.append(ctx)
            return text
        monkeypatch.setattr(scopes, "program_hlo", program_hlo)
        return calls
    return use


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/dot_general", None),
    ("jit(train_step)/while/body/closed_call/jvp()/while/body/closed_call/"
     "attn/bsd,dhk->bshk/dot_general", "attn"),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(head))/mul;"
     "transpose(jvp(head))/mul", "head"),
    ("jit(train_step)/transpose(jvp(norm))/reduce_sum", "norm"),
    # the innermost scope wins: a shared expert's MLP inside an MoE
    ("jit(train_step)/attn/checkpoint/mlp/add", "mlp"),
    ("jit(train_step)/opt/mlp_gate/add", "opt"),
    ("state[0]['embed']['head']", None),
    ("", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_op_names_of_every_instruction():
    module, names = scopes.op_names(HLO_A)
    assert module == "jit_a"
    assert names == {"param_0": "", "tanh.1": "jit(a)/mlp/tanh", "x": "",
                     "fusion.1": "jit(a)/mlp/tanh",
                     "fusion.2": "jit(a)/opt/add", "copy.3": "jit(a)/copy"}


def synthetic():
    """One chip: jit_a runs 0-50 ns (mlp 0-30, opt 30-40, a copy of no
    scope 40-50), jit_b 60-100 ns with the same instruction names."""
    d = Device("/device:TPU:0",
               ops=[Op(0, 30, "%fusion.1 = bf16[8] fusion()"),
                    Op(30, 40, "%fusion.2 = f32[8] fusion()"),
                    Op(40, 50, "%copy.3 = f32[8] copy()"),
                    Op(60, 90, "%fusion.1 = bf16[8] fusion()"),
                    Op(90, 100, "%fusion.2 = f32[8] fusion()")],
               modules=[Op(0, 50, "jit_a(1)"), Op(60, 100, "jit_b(2)")])
    return Trace([d], [("bench.window", 0, 100)], (0, 100))


def test_synthetic_seconds_by_scope():
    tr = synthetic()
    s = scopes.seconds_by_scope(tr, HLO_A)
    # jit_b is another program: its ops have no scope
    assert s == {"mlp": pytest.approx(30e-9), "opt": pytest.approx(10e-9),
                 None: pytest.approx(50e-9)}
    assert sum(s.values()) == pytest.approx(tr.busy_s(tr.devices[0]))


def test_scopes_clipped_to_window():
    tr = synthetic()
    tr.window = (20, 95)
    s = scopes.seconds_by_scope(tr, HLO_A)
    assert s == {"mlp": pytest.approx(10e-9), "opt": pytest.approx(10e-9),
                 None: pytest.approx(45e-9)}


def test_mean_over_chips():
    tr = synthetic()
    d1 = Device("/device:TPU:1", ops=[Op(0, 10, "%fusion.2 = f32[8] x()")],
                modules=[Op(0, 10, "jit_a(1)")])
    tr.devices.append(d1)
    s = scopes.seconds_by_scope(tr, HLO_A)
    assert s["mlp"] == pytest.approx(15e-9)
    assert s["opt"] == pytest.approx(10e-9)


def test_op_outside_the_program_has_no_scope():
    d = Device("/device:TPU:0",
               ops=[Op(0, 10, "%fusion.1 = bf16[8] fusion()"),
                    Op(15, 25, "%fusion.1 = bf16[8] fusion()")],
               modules=[Op(0, 20, "jit_a(1)")])
    s = scopes.seconds_by_scope(Trace([d], [], (0, 30)), HLO_A)
    assert s == {"mlp": pytest.approx(10e-9), None: pytest.approx(10e-9)}


def test_another_program_compiled_reads_none():
    """An op of the program that the compiled text lacks: the program
    compiled for the readers is not the one that ran."""
    tr = synthetic()
    tr.devices[0].ops.append(Op(45, 48, "%fusion.9 = f32[8] fusion()"))
    assert scopes.seconds_by_scope(tr, HLO_A) is None
    assert scopes.seconds_by_scope(
        tr, HLO_A.replace("HloModule jit_a", "HloModule jit_c")) is None


def test_readers_on_synthetic_trace(hlo_is):
    calls = hlo_is(HLO_A)
    ctx = Ctx(synthetic(), traced_steps=2)
    assert reader("mlp_ms.train").read(ctx) == pytest.approx(1e3 * 15e-9)
    assert reader("opt_ms.train").read(ctx) == pytest.approx(1e3 * 5e-9)
    assert reader("attn_ms.train").read(ctx) is None
    assert reader("unscoped_share.train").read(ctx) == pytest.approx(
        100 * 50 / 90)
    # one compile for all the readers of a trace
    assert len(calls) == 1
    reader("head_ms.train").read(Ctx(synthetic()))
    assert len(calls) == 2


@pytest.mark.parametrize("name", READERS)
def test_readers_none_without_program(name, hlo_is):
    hlo_is(None)
    assert reader(name).read(Ctx(synthetic())) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_none_for_a_program_without_scopes(name, hlo_is):
    hlo_is(HLO_A.replace("/mlp/", "/").replace("/opt/", "/"))
    assert reader(name).read(Ctx(synthetic())) is None


def test_program_hlo_only_for_one_chip_training():
    ctx = Ctx(synthetic())
    ctx.config = {"kind": "serve"}
    assert scopes.program_hlo(ctx) is None
    ctx.config = {"kind": "train", "train": {"mesh": [2, 2]}}
    assert scopes.program_hlo(ctx) is None


def recorded():
    tr = Trace.from_file(str(DATA / "trace_scoped_1chip.xplane.pb"))
    return tr, (DATA / "trace_scoped_1chip.hlo.txt").read_text()


def test_recorded_scoped_trace(hlo_is):
    """``record_scoped_trace.py``: a product under ``mlp``, an update under
    ``opt`` and a running sum under no scope, three times; the sum and its
    layout copies take between a tenth and a half of the busy time."""
    tr, hlo = recorded()
    s = scopes.seconds_by_scope(tr, hlo)
    assert s["mlp"] > 0 and s["opt"] > 0
    busy = tr.busy_s(tr.devices[0])
    assert sum(s.values()) == pytest.approx(
        tr.op_seconds(tr.devices[0], lambda o: True)[0])
    assert sum(s.values()) == pytest.approx(busy, rel=0.01)
    hlo_is(hlo)
    ctx = Ctx(tr, traced_steps=3)
    assert 10.0 < reader("unscoped_share.train").read(ctx) < 50.0
    assert reader("mlp_ms.train").read(ctx) == pytest.approx(
        1e3 * s["mlp"] / 3)


def test_reading_scopes_leaves_the_trace_alone(hlo_is):
    """Every existing reduction reads the same after the scope readers."""
    tr, hlo = recorded()
    before = (tr.window, tr.mean_busy_s(), tr.idle_share(), tr.device_ops(),
              tr.idle_gaps(), [(o.start, o.end, o.text)
                               for o in tr.ops(tr.devices[0])])
    hlo_is(hlo)
    for name in READERS:
        reader(name).read(Ctx(tr, traced_steps=3))
    assert before == (tr.window, tr.mean_busy_s(), tr.idle_share(),
                      tr.device_ops(), tr.idle_gaps(),
                      [(o.start, o.end, o.text)
                       for o in tr.ops(tr.devices[0])])


def test_compiled_step_of_a_small_cell():
    """The step the readers compile carries every scope of the training
    path, and a second build compiles to the same instruction names."""
    H.import_program()
    config = H.load_json(H.BENCH / "configs" / "gpt3-xl-6L.json")
    config["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                           head_dim=16, d_ff=128, vocab_size=257)
    config["train"].update(accum_steps=2, mesh=None)
    traffic = H.load_json(H.BENCH / "traffic" / "paper-batch40.json")
    traffic.update(global_batch=4, seq_len=64)
    devices = jax.devices()[:1]
    module, names = scopes.op_names(
        scopes.compiled_step(config, traffic, devices))
    assert module == "jit_train_step"
    assert {scopes.scope_of(n) for n in names.values()} == \
        set(scopes.SCOPES) | {None}
    assert scopes.op_names(
        scopes.compiled_step(config, traffic, devices)) == (module, names)
