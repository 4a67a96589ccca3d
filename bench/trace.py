"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the profiler writes one plane per chip (``/device:TPU:<n>``)
with the lines ``XLA Modules`` (one event per run of a jitted program,
named ``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per
executed HLO instruction, named by the instruction's text: ``%fusion.12 =
bf16[8,1024]{...} fusion(...)``; a ``while`` loop is an event that holds
the events of its body) and ``Async XLA Ops`` (the flight of asynchronous
copies and collectives), and host planes whose thread lines hold the
``jax.profiler.TraceAnnotation`` spans of the harness (``bench.*``).
Timestamps are in nanoseconds.

The device planes' clock runs apart from the host's by up to about a
millisecond (a program can appear to start before the host dispatched
it), so each chip's events are shifted to the host clock by its earliest
launch: the smallest gap between a program's start on the chip and the
host's dispatch of it (``PjitFunction(...)`` spans, in order) is taken
as zero.

The harness opens the host span ``bench.window`` around the traced part
of a run; everything here is clipped to it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
_OP = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclass
class Op:
    """One executed HLO instruction on one device."""
    start: float          # ns
    end: float            # ns
    text: str             # the instruction as the profiler names it

    @property
    def duration(self) -> float:
        return self.end - self.start

    def _parts(self):
        m = _OP.match(self.text)
        if not m:
            return self.text.split(" ")[0].lstrip("%"), "", ""
        return m.group(1), _LAYOUT.sub("", m.group(2)), m.group(3)

    @property
    def base(self) -> str:
        """Instruction name without its number: ``fusion``, ``copy``."""
        return re.sub(r"\.\d+$", "", self._parts()[0])

    @property
    def opcode(self) -> str:
        return self._parts()[2]

    @property
    def shape(self) -> str:
        """Result shape without layouts: ``bf16[8,16,128]``."""
        return self._parts()[1]

    @property
    def custom_call_target(self) -> Optional[str]:
        m = re.search(r'custom_call_target="([^"]+)"', self.text)
        return m.group(1) if m else None

    @property
    def operand_shapes(self) -> List[str]:
        """Operand shapes without layouts, in order."""
        m = re.search(r" [a-z][\w\-]*\((.*?)\)(, [a-z_]+=|$)", self.text)
        if not m:
            return []
        inner = _LAYOUT.sub("", m.group(1))
        return re.findall(r"([a-z]+\d*\[[\d,]*\])", inner)

    @property
    def is_collective(self) -> bool:
        return any(self.base.startswith(c) or self.opcode.startswith(c)
                   for c in COLLECTIVES)

    @property
    def label(self) -> str:
        target = self.custom_call_target
        kind = f" {target}" if target else ""
        return f"{self.base}{kind} {self.shape}"


@dataclass
class Device:
    name: str
    ops: List[Op] = field(default_factory=list)        # leaf ops
    asyncs: List[Op] = field(default_factory=list)     # async flights
    modules: List[Op] = field(default_factory=list)    # program runs


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a`` outside the merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events: List[Op]) -> List[Op]:
    """Events that hold no other event (a ``while`` holds its body)."""
    evs = sorted(events, key=lambda o: (o.start, -o.end))
    container = [False] * len(evs)
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            container[stack[-1]] = True
        stack.append(i)
    return [e for e, c in zip(evs, container) if not c]


class Trace:
    """The device planes and host spans of one trace, clipped to the
    ``bench.window`` span."""

    def __init__(self, devices: List[Device], spans: List[Tuple],
                 window: Tuple[float, float]):
        self.devices = devices
        self.spans = spans                  # (name, start, end), host
        self.window = window

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        devices, spans, dispatch = [], [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:") and \
                    not plane.name.startswith("/device:CUSTOM"):
                dev = Device(plane.name)
                for line in plane.lines:
                    evs = [Op(e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in line.events]
                    if line.name == "XLA Ops":
                        dev.ops = leaves(evs)
                    elif line.name == "Async XLA Ops":
                        dev.asyncs = evs
                    elif line.name == "XLA Modules":
                        dev.modules = evs
                if dev.ops or dev.modules:
                    devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            spans.append((e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
                        elif e.name.startswith("PjitFunction("):
                            dispatch.append(Op(e.start_ns, e.start_ns
                                               + e.duration_ns, e.name))
        devices.sort(key=lambda d: _device_index(d.name))
        launches = [o.start for o in _outermost(dispatch)]
        for dev in devices:
            _align(dev, launches)
        wins = [(s, e) for n, s, e in spans if n == WINDOW]
        if wins:
            window = (min(s for s, _ in wins), max(e for _, e in wins))
        else:
            ts = [(o.start, o.end) for d in devices for o in d.ops]
            window = (min(s for s, _ in ts), max(e for _, e in ts)) \
                if ts else (0.0, 0.0)
        return cls(devices, spans, window)

    # -- clipping ---------------------------------------------------------
    def _clip(self, ops: List[Op]) -> List[Op]:
        w0, w1 = self.window
        return [Op(max(o.start, w0), min(o.end, w1), o.text) for o in ops
                if o.end > w0 and o.start < w1]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, dev: Device) -> List[Op]:
        return self._clip(dev.ops)

    def modules(self, dev: Device) -> List[Op]:
        return self._clip(dev.modules)

    # -- reductions -------------------------------------------------------
    def busy_s(self, dev: Device) -> float:
        """Seconds in which an operation ran on ``dev``."""
        return length(union((o.start, o.end) for o in self.ops(dev))) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def op_seconds(self, dev: Device, predicate) -> Tuple[float, int]:
        """Summed device seconds and count of the ops ``predicate`` takes."""
        sel = [o for o in self.ops(dev) if predicate(o)]
        return sum(o.duration for o in sel) * 1e-9, len(sel)

    def module_seconds(self, dev: Device, predicate) -> Tuple[float, int]:
        """Summed device seconds and runs of the programs ``predicate``
        takes (by module name)."""
        sel = [m for m in self.modules(dev) if predicate(m.text)]
        return sum(m.duration for m in sel) * 1e-9, len(sel)

    def collective_s(self, dev: Device) -> Tuple[float, float]:
        """(seconds with a collective in flight, the part of them with no
        other operation running) on ``dev``."""
        coll = union([(o.start, o.end) for o in self.ops(dev)
                      if o.is_collective]
                     + [(o.start, o.end) for o in self._clip(dev.asyncs)
                        if o.is_collective])
        compute = union((o.start, o.end) for o in self.ops(dev)
                        if not o.is_collective)
        return length(coll) * 1e-9, length(subtract(coll, compute)) * 1e-9

    def device_ops(self, top: int = 10) -> List[List]:
        """The operations that took most device time, by label, as mean
        seconds per chip."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            for o in self.ops(d):
                tot[o.label] = tot.get(o.label, 0.0) + o.duration * 1e-9
        n = len(self.devices)
        return [[k, v / n] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The longest idle gaps of the first chip, each named by the
        innermost harness span open at its middle."""
        if not self.devices:
            return []
        busy = union((o.start, o.end) for o in self.ops(self.devices[0]))
        gaps = subtract([self.window], busy)
        inner = [s for s in self.spans if s[0] != WINDOW]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = (s + e) / 2
            open_ = [sp for sp in inner if sp[1] <= mid < sp[2]]
            name = min(open_, key=lambda sp: sp[2] - sp[1])[0] \
                if open_ else "none"
            out.append([name, (e - s) * 1e-9])
        return out


def _outermost(events: List[Op]) -> List[Op]:
    """Events that no other event holds (a dispatch nests its own)."""
    out: List[Op] = []
    for e in sorted(events, key=lambda o: (o.start, -o.end)):
        if not out or e.start >= out[-1].end:
            out.append(e)
    return out


def _align(dev: Device, launches: List[float]) -> None:
    """Shift ``dev``'s events to the host clock by its earliest launch."""
    runs = sorted(m.start for m in dev.modules)
    k = min(len(runs), len(launches))
    if not k:
        return
    shift = min(r - h for r, h in zip(runs[:k], launches[:k]))
    for events in (dev.ops, dev.asyncs, dev.modules):
        for o in events:
            o.start -= shift
            o.end -= shift


def _device_index(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0
