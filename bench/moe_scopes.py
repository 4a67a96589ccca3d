"""Device time by the sub-scopes of a dropless expert layer, for the
``moe_*_ms.train`` readers.

The program nests a second vocabulary inside the ``mlp`` scope of
``models/common.py:apply_routed_moe`` (``repro.obs.scopes.MOE_SCOPES``):
``router`` (scores, bias, top-k, weights, the loads and balance loss),
``dispatch`` (sort, permute, gather and the weighted combine), ``experts``
(the grouped matmuls) and ``shared`` (the shared expert).  As in
``scopes.py``, each op of the cell's program takes the innermost component
of its ``op_name`` that is one of :data:`MOE_SCOPES`, read from the
optimized HLO of the step compiled once more after the run
(``scopes.compiled_step``, from the persistent compilation cache).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Optional

from bench import scopes

# repro.obs.scopes.MOE_SCOPES, held here so that the benchmark does not
# import the program to read its yardstick
MOE_SCOPES = ("router", "dispatch", "experts", "shared")

# the trace last read and its seconds by sub-scope: one compile for all
_last: list = [None, None]


def moe_scope_of(op_name: str) -> Optional[str]:
    """The innermost component of an ``op_name`` path that is one of
    :data:`MOE_SCOPES` (transformations and ``;``-merged names as in
    ``scopes.scope_of``)."""
    for part in reversed(re.split(r"[/;()]", op_name)):
        if part in MOE_SCOPES:
            return part
    return None


def seconds_by_moe_scope(trace, hlo: str
                         ) -> Optional[Dict[Optional[str], float]]:
    """Device seconds of the ops in each sub-scope (None: in none), clipped
    to the window, the mean over chips; None where no op ran in the program
    of ``hlo`` or one of its ops is not in ``hlo``."""
    module, names = scopes.op_names(hlo)
    total: Dict[Optional[str], float] = {}
    found = False
    for dev in trace.devices:
        runs = [m for m in trace.modules(dev)
                if m.text.split("(")[0] == module]
        starts = [m.start for m in runs]
        for o in trace.ops(dev):
            i = bisect.bisect_right(starts, o.start) - 1
            if i < 0 or o.end > runs[i].end:
                continue
            inst = scopes._EVENT.match(o.text)
            if not inst or inst.group(1) not in names:
                return None
            found = True
            key = moe_scope_of(names[inst.group(1)])
            total[key] = total.get(key, 0.0) + o.duration * 1e-9
    if not found:
        return None
    return {k: v / len(trace.devices) for k, v in total.items()}


def by_moe_scope(ctx) -> Optional[Dict[Optional[str], float]]:
    """:func:`seconds_by_moe_scope` of the cell's trace and program,
    computed once for all the readers of a run."""
    if _last[0] is not ctx.trace:
        hlo = scopes.program_hlo(ctx)
        _last[:] = [ctx.trace, hlo and seconds_by_moe_scope(ctx.trace, hlo)]
    return _last[1]


def step_ms(ctx, name: str) -> Optional[float]:
    """Device ms per traced step in sub-scope ``name``, the mean over
    chips; None where no op ran in it (a program without expert layers)."""
    s = by_moe_scope(ctx)
    steps = ctx.counts.get("traced_steps")
    return 1e3 * s[name] / steps if s and s.get(name) and steps else None
