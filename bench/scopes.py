"""Device time by the program's named scopes, for the ``*_ms.train`` and
``unscoped_share.train`` readers.

``jax.named_scope`` writes its name into the ``op_name`` metadata of every
HLO instruction traced under it (``jit(step)/while/body/.../mlp/
dot_general``), and the compiler keeps it: a fusion takes the ``op_name``
XLA gives the fusion instruction, which is that of its root.  The trace's
device events name each instruction (``%fusion.12 = ...``) but not its
``op_name``, and the harness removes the trace file before the readers
run.  So the ``op_name``s come from the optimized HLO of the cell's step,
compiled once more for the cell's shapes after the run: the persistent
compilation cache, which holds every program of the run, gives back the
executable that ran.

An op inside a run of that program (the ``XLA Modules`` event that holds
it) takes the innermost component of its instruction's ``op_name`` that
is one of :data:`SCOPES`; every other op has no scope.  Where an op of the
program is not in the compiled text, the program compiled here is not the
one that ran, and every reader returns None.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Tuple

# the program's named scopes (repro.obs.scopes.SCOPES), held here so that
# the benchmark does not import the program to read its yardstick
SCOPES = ("embed", "attn", "mlp", "norm", "head", "grad_accum", "opt")

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r', metadata=\{[^\n]*?op_name="((?:[^"\\]|\\.)*)"')
_EVENT = re.compile(r"^%?([\w.\-]+)")

# the trace last read and its seconds by scope: one compile for all readers
_last: list = [None, None]


def scope_of(op_name: str) -> Optional[str]:
    """The innermost component of an ``op_name`` path that is one of
    :data:`SCOPES`.  Transformations wrap a component
    (``transpose(jvp(mlp))``) and XLA joins the names of merged
    instructions with ``;``."""
    for part in reversed(re.split(r"[/;()]", op_name)):
        if part in SCOPES:
            return part
    return None


def op_names(hlo: str) -> Tuple[str, Dict[str, str]]:
    """The module name of the HLO text ``hlo`` and the ``op_name`` of each
    of its instructions, fused ones included (``""`` where it has none)."""
    m = _MODULE.search(hlo)
    names: Dict[str, str] = {}
    for line in hlo.splitlines():
        inst = _INSTRUCTION.match(line)
        if inst:
            meta = _OP_NAME.search(line)
            names[inst.group(1)] = meta.group(1) if meta else ""
    return (m.group(1) if m else ""), names


def seconds_by_scope(trace, hlo: str) -> Optional[Dict[Optional[str], float]]:
    """Device seconds of the ops in each scope (None: in no scope), clipped
    to the window and the mean over chips; None where no op ran in the
    program of ``hlo``, or one of its ops is not in ``hlo``."""
    module, names = op_names(hlo)
    total: Dict[Optional[str], float] = {}
    found = False
    for dev in trace.devices:
        runs = [m for m in trace.modules(dev)
                if m.text.split("(")[0] == module]
        starts = [m.start for m in runs]
        for o in trace.ops(dev):
            i = bisect.bisect_right(starts, o.start) - 1
            scope = None
            if i >= 0 and o.end <= runs[i].end:
                inst = _EVENT.match(o.text)
                if not inst or inst.group(1) not in names:
                    return None
                scope = scope_of(names[inst.group(1)])
                found = True
            total[scope] = total.get(scope, 0.0) + o.duration * 1e-9
    if not found:
        return None
    return {k: v / len(trace.devices) for k, v in total.items()}


def compiled_step(config: dict, traffic: dict, devices) -> str:
    """The optimized HLO text of a one-chip training cell's jitted step,
    built as ``bench/train.py`` builds it and compiled for the state and
    batch shapes."""
    import jax
    import jax.numpy as jnp

    from bench import harness as H, train
    step, init, _, ctx, _ = train.build(config, traffic, H.Seeds(0), devices)
    one = jax.sharding.SingleDeviceSharding(devices[0])
    with ctx:
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(init, jax.random.PRNGKey(0)))
        shape = (traffic["global_batch"], traffic["seq_len"])
        batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
                 for k in ("tokens", "targets")}
        return step.lower(state, batch).compile().as_text()


def program_hlo(ctx) -> Optional[str]:
    """The optimized HLO of the program the cell ran; None for a cell that
    is not a one-chip training cell."""
    if ctx.config.get("kind") != "train" or ctx.config["train"].get("mesh"):
        return None
    import jax
    return compiled_step(ctx.config, ctx.traffic, jax.devices()[:1])


def by_scope(ctx) -> Optional[Dict[Optional[str], float]]:
    """:func:`seconds_by_scope` of the cell's trace and program, computed
    once for all the readers of a run."""
    if _last[0] is not ctx.trace:
        hlo = program_hlo(ctx)
        _last[:] = [ctx.trace, hlo and seconds_by_scope(ctx.trace, hlo)]
    return _last[1]


def step_ms(ctx, name: str) -> Optional[float]:
    """Device ms per traced step in scope ``name``, the mean over chips;
    None where no op ran in it (a program without the scope)."""
    s = by_scope(ctx)
    steps = ctx.counts.get("traced_steps")
    return 1e3 * s[name] / steps if s and s.get(name) and steps else None


def unscoped_share(ctx) -> Optional[float]:
    """Share, in %, of the device's busy time in ops of no scope; None
    where no op has a scope (a program without them)."""
    s = by_scope(ctx)
    if not s or not any(k is not None for k in s):
        return None
    busy = ctx.trace.mean_busy_s()
    return 100.0 * s.get(None, 0.0) / busy if busy else None
