"""A profiler trace (``.xplane.pb``) reduced to the numbers the per-layer
metrics read.

* the traced window: from the first to the last benchmark span
  (``bench.pump``) on the host, on the trace's own clock;
* device busy time: the union of the intervals in which an operation ran
  on a device (overlapping operations count once), inside the window,
  and the idle share that leaves;
* time per program (the ``XLA Modules`` line) and per operation or
  kernel (the ``XLA Ops`` line, each operation in the program whose
  interval holds it), by stable name: a name that matches nothing
  raises;
* idle gaps, each labelled by the innermost benchmark span the host was
  in at the gap's midpoint.

The trace is read with ``jax.profiler.ProfileData`` into plain tuples, so
the reduction itself can be checked on synthetic traces.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.pump"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str) -> Optional[object]:
        for k, v in self.stats:
            if k == key:
                return v
        return None


@dataclasses.dataclass(frozen=True)
class Line:
    name: str
    events: Tuple[Event, ...]


@dataclasses.dataclass(frozen=True)
class Plane:
    name: str
    lines: Tuple[Line, ...]


def read_xplane(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            evs = tuple(Event(e.name, float(e.start_ns), float(e.duration_ns),
                              tuple((k, v) for k, v in e.stats))
                        for e in ln.events)
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, tuple(lines)))
    return planes


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals so that overlapping ones count once."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def module_name(name: str) -> str:
    """``jit_fused(123)`` -> ``jit_fused``: a program's name without the
    run-specific id."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: an
    operation's instruction name without its HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")


# operations whose interval holds other operations of the same line
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


class Reduced:
    def __init__(self, planes: Sequence[Plane]):
        self.devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
        if not self.devices:
            raise ValueError("the trace holds no TPU device plane")
        spans = [e for p in planes if not DEVICE_PLANE.match(p.name)
                 for ln in p.lines for e in ln.events if e.name.startswith(SPAN_PREFIX)]
        pumps = [e for e in spans if e.name == WINDOW_SPAN]
        if not pumps:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        self.t0 = min(e.start_ns for e in pumps)
        self.t1 = max(e.end_ns for e in pumps)
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self._ops = {p.name: self._line(p, OPS_LINE) for p in self.devices}
        self._modules = {p.name: sorted(self._line(p, MODULES_LINE), key=lambda e: e.start_ns)
                         for p in self.devices}
        self._module_starts = {n: [m.start_ns for m in mods] for n, mods in self._modules.items()}
        self._span_starts = [e.start_ns for e in self.spans]
        # devices that ran anything in the window are the ones in use
        self.used = [n for n, evs in self._ops.items() if evs] or [self.devices[0].name]
        self._busy = {n: union(_clip([(e.start_ns, e.end_ns) for e in self._ops[n]],
                                     self.t0, self.t1)) for n in self.used}

    def _dur(self, e: Event) -> float:
        """Seconds of ``e`` inside the window."""
        return (min(e.end_ns, self.t1) - max(e.start_ns, self.t0)) / 1e9

    def _line(self, plane: Plane, name: str) -> List[Event]:
        evs = [e for ln in plane.lines if ln.name == name for e in ln.events]
        return [e for e in evs if e.end_ns > self.t0 and e.start_ns < self.t1]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices in use."""
        return sum(sum(e - s for s, e in iv) for iv in self._busy.values()) / len(self._busy) / 1e9

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_seconds(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        evs = [e for n in self.used for e in self._modules[n] if rx.search(module_name(e.name))]
        if not evs:
            raise KeyError(f"no program matching {pattern!r} in the trace; programs: "
                           f"{sorted({module_name(e.name) for n in self.used for e in self._modules[n]})}")
        return sum(self._dur(e) for e in evs) / len(self.used)

    def _module_of(self, device: str, e: Event) -> str:
        mods = self._modules[device]
        i = bisect.bisect_right(self._module_starts[device], e.start_ns) - 1
        if i >= 0 and e.start_ns < mods[i].end_ns:
            return module_name(mods[i].name)
        return ""

    def op_seconds(self, pattern: str, program: Optional[str] = None) -> float:
        """Device seconds of the operations whose HLO text matches
        ``pattern``; ``program`` narrows them to the programs whose name
        matches it."""
        rx = re.compile(pattern)
        prx = re.compile(program) if program else None
        total, hit = 0.0, False
        for n in self.used:
            for e in self._ops[n]:
                if not rx.search(e.name):
                    continue
                if prx is not None and not prx.search(self._module_of(n, e)):
                    continue
                total += self._dur(e)
                hit = True
        if not hit:
            raise KeyError(f"no device operation matching {pattern!r}"
                           + (f" in programs {program!r}" if program else "") + " in the trace")
        return total / len(self.used)

    def top_ops(self, k: int = 10) -> List[List]:
        """The operations that took most device time, as
        ``program/instruction``; loops and calls are left out for the
        operations inside them."""
        by: Dict[str, float] = defaultdict(float)
        for n in self.used:
            for e in self._ops[n]:
                op = op_name(e.name)
                if not CONTAINERS.match(op):
                    by[f"{self._module_of(n, e)}/{op}"] += self._dur(e) / len(self.used)
        return [[name, s] for name, s in sorted(by.items(), key=lambda x: -x[1])[:k]]

    def _label(self, t: float) -> str:
        """The innermost benchmark span open at ``t``."""
        best = None
        for e in self.spans[:bisect.bisect_right(self._span_starts, t)]:
            if e.start_ns <= t < e.end_ns and (best is None or e.start_ns >= best.start_ns):
                best = e
        return best.name if best is not None else "outside bench spans"

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds of the first device in use, summed by what the host
        was doing: each gap is cut at the host spans' edges and each piece
        goes to the innermost span open over it.  Largest first."""
        busy = self._busy[self.used[0]]
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        cuts = sorted({t for e in self.spans for t in (e.start_ns, e.end_ns)})
        by: Dict[str, float] = defaultdict(float)
        for s, e in zip(edges[0::2], edges[1::2]):
            inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
            pts = [s] + inner + [e]
            for a, b in zip(pts, pts[1:]):
                if b > a:
                    by[self._label((a + b) / 2)] += (b - a) / 1e9
        return [[name, s] for name, s in sorted(by.items(), key=lambda x: -x[1])[:k]]


def reduce_dir(log_dir: str) -> Reduced:
    return Reduced(read_xplane(find_xplane(log_dir)))
