"""The serving program's own spans in a traced run.

``repro.serve.tracing`` writes ``serve.*`` and ``frontend.*`` spans into
the profiler's trace, with the work each did as the event's stats, on the
clock of the device planes.  This module reads them from the traced run's
``.xplane.pb`` (once per file), keeps the spans that start inside the
window ``trace_reduce.Reduced`` found, and reduces them against its
device busy intervals:

* sums of a stat over the spans of one name;
* each round's host time: a ``serve.step`` span less the
  ``serve.host_sync`` spans inside it;
* device-idle time by the innermost program span open over it.

A trace without these spans (a program from before them) makes the
readers raise ``KeyError``, and their metrics are left out of the result
line.  The span names are written out here, not imported from
``repro.serve.tracing``, for the same reason.
"""
from __future__ import annotations

import functools
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from bench import trace_reduce
from bench.trace_reduce import DEVICE_PLANE, Event, Plane, Reduced

PREFIXES = ("serve.", "frontend.")
STEP = "serve.step"
PREFILL_CHUNK = "serve.prefill_chunk"
DECODE_DISPATCH = "serve.decode_dispatch"
HOST_SYNC = "serve.host_sync"
OUTSIDE = "outside program spans"


def program_events(planes: Sequence[Plane]) -> Tuple[Event, ...]:
    """The program's spans on the host planes, outer before inner."""
    evs = [e for p in planes if not DEVICE_PLANE.match(p.name)
           for ln in p.lines for e in ln.events if e.name.startswith(PREFIXES)]
    return tuple(sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns)))


@functools.lru_cache(maxsize=1)
def load(path: str) -> Tuple[Event, ...]:
    return program_events(trace_reduce.read_xplane(path))


def of(r) -> "Spans":
    """The spans of a traced run's readings (``harness.Readings``)."""
    if r.trace is None:
        raise ValueError("not a traced run")
    # where harness._measure has the profiler write
    log_dir = r.cell.root / ".bench_trace" / r.cell.name
    try:
        path = trace_reduce.find_xplane(str(log_dir))
    except FileNotFoundError as e:
        raise KeyError(str(e)) from e
    return Spans(load(path), r.trace)


def segments(events: Sequence[Event], t0: float, t1: float) -> List[Tuple[float, float, str]]:
    """``[t0, t1)`` cut at the spans' edges, each piece labelled by the
    innermost span open over it (spans nest, as on one thread)."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    t = t0

    def cut(until: float, label: str) -> None:
        nonlocal t
        until = min(until, t1)
        if until > t:
            segs.append((t, until, label))
            t = until

    for e in events:
        while stack and stack[-1].end_ns <= e.start_ns:
            cut(stack[-1].end_ns, stack.pop().name)
        cut(e.start_ns, stack[-1].name if stack else OUTSIDE)
        stack.append(e)
    while stack:
        cut(stack[-1].end_ns, stack.pop().name)
    cut(t1, OUTSIDE)
    return segs


class Spans:
    def __init__(self, events: Sequence[Event], reduced: Reduced):
        self.trace = reduced
        self.events = [e for e in events if reduced.t0 <= e.start_ns < reduced.t1]

    def named(self, name: str) -> List[Event]:
        evs = [e for e in self.events if e.name == name]
        if not evs:
            raise KeyError(f"no {name!r} span in the traced window")
        return evs

    def total(self, name: str, key: str) -> float:
        """``key`` summed over the ``name`` spans that carry it."""
        vals = [e.stat(key) for e in self.named(name)]
        vals = [float(v) for v in vals if v is not None]
        if not vals:
            raise KeyError(f"no {name!r} span in the traced window carries {key!r}")
        return sum(vals)

    def round_host_ms(self) -> List[float]:
        """Each round's milliseconds less the host syncs inside it."""
        syncs = [e for e in self.events if e.name == HOST_SYNC]
        return [(s.dur_ns - sum(h.dur_ns for h in syncs
                                if s.start_ns <= h.start_ns and h.end_ns <= s.end_ns)) / 1e6
                for s in self.named(STEP)]

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the first device in use, by the innermost
        program span open over them."""
        tr = self.trace
        busy = tr._busy[tr.used[0]]
        edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        segs = segments(self.events, tr.t0, tr.t1)
        by: Dict[str, float] = defaultdict(float)
        i = 0
        for s, e in idle:
            while i < len(segs) and segs[i][1] <= s:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < e:
                a, b, label = segs[j]
                by[label] += (min(b, e) - max(a, s)) / 1e9
                j += 1
        return dict(by)
