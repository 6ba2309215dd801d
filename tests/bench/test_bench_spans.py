"""The serving program's spans reduced to per-layer numbers, on synthetic
traces: device-idle time goes to the innermost program span open over
it, a round's host time leaves out its host syncs, spans that start
outside the window are left out, and a trace without the spans makes the
readers raise."""
import os
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import harness, spans  # noqa: E402
from bench.trace_reduce import Event, Line, Plane, Reduced  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
MS = 1e6  # ns


def ev(name, start_ms, end_ms, **stats):
    return Event(name, start_ms * MS, (end_ms - start_ms) * MS, tuple(stats.items()))


# one round inside the window [0, 100) ms, and one after it
PROGRAM = [
    ev("frontend.pump", 1, 99),
    ev("serve.step", 2, 98, step_num=1),
    ev("serve.admit", 3, 10),
    ev("serve.prefill_chunk", 10, 30, rows=2, tokens=600, width=512),
    ev("serve.decode_dispatch", 30, 35, steps=8),
    ev("serve.host_sync", 35, 80),
    ev("serve.retire", 80, 95),
    ev("serve.accounting", 85, 90),
    ev("serve.step", 150, 160, step_num=2),
    ev("serve.decode_dispatch", 151, 152, steps=4),
]
BENCH = [ev("bench.pump", 0, 100)]
MODULES = [ev("jit_f(1)", 12, 28), ev("jit_fused(2)", 36, 79)]
OPS = [ev("%fusion.1 = bf16[8] fusion(...)", 12, 28),
       ev("%paged_attention_kernel.3 = (f32[3,32,1,64]) custom-call(...)", 36, 79)]


def planes(program=PROGRAM):
    return [Plane("/host:CPU", (Line("python", tuple(BENCH + program)),)),
            Plane("/device:TPU:0", (Line("XLA Modules", tuple(MODULES)),
                                    Line("XLA Ops", tuple(OPS))))]


@pytest.fixture
def readings(tmp_path, monkeypatch):
    """Readings of a traced run whose trace is ``planes()``."""
    def make(program=PROGRAM):
        trace_dir = tmp_path / ".bench_trace" / "cell" / "plugins"
        trace_dir.mkdir(parents=True, exist_ok=True)
        (trace_dir / "t.xplane.pb").write_bytes(b"")
        ps = planes(program)
        monkeypatch.setattr(spans, "load", lambda path: spans.program_events(ps))
        return types.SimpleNamespace(trace=Reduced(ps),
                                     cell=types.SimpleNamespace(root=tmp_path, name="cell"))
    return make


def test_idle_goes_to_the_innermost_program_span():
    s = spans.Spans(spans.program_events(planes()), Reduced(planes()))
    idle = s.idle_by_span()
    # idle [0,12], [28,36], [79,100] ms, cut at the spans' edges
    want = {spans.OUTSIDE: 2, "frontend.pump": 2, "serve.step": 4, "serve.admit": 7,
            "serve.prefill_chunk": 4, "serve.decode_dispatch": 5, "serve.host_sync": 2,
            "serve.retire": 10, "serve.accounting": 5}
    assert idle == pytest.approx({k: v / 1e3 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(s.trace.window_s * s.trace.idle_pct / 100)


def test_segments_nest_and_cover_the_window():
    evs = spans.program_events([Plane("/host:CPU", (Line("python", (
        ev("serve.retire", 10, 20), ev("serve.step", 10, 30), ev("serve.accounting", 12, 14))),))])
    assert [e.name for e in evs] == ["serve.step", "serve.retire", "serve.accounting"]
    segs = [(a / MS, b / MS, n) for a, b, n in spans.segments(evs, 0, 40 * MS)]
    assert segs == [(0, 10, spans.OUTSIDE), (10, 12, "serve.retire"),
                    (12, 14, "serve.accounting"), (14, 20, "serve.retire"),
                    (20, 30, "serve.step"), (30, 40, spans.OUTSIDE)]


def test_round_host_time_leaves_out_host_syncs_and_the_window_edges():
    s = spans.Spans(spans.program_events(planes()), Reduced(planes()))
    assert s.round_host_ms() == pytest.approx([96 - 45])
    assert s.total(spans.DECODE_DISPATCH, "steps") == 8  # the round at 150 ms is outside
    with pytest.raises(KeyError, match="carries"):
        s.total(spans.DECODE_DISPATCH, "width")


def test_readers_read_the_span_stats(readings):
    r = readings()
    read = {name: harness.reader(ROOT, name + ".longdoc")(r) for name in (
        "decode_ms_per_step", "prefill_pad_pct", "host_ms_per_round", "host_bound_idle_pct")}
    assert read["decode_ms_per_step"] == pytest.approx(43 / 8)
    assert read["prefill_pad_pct"] == pytest.approx(100 * (1 - 600 / 1024))
    assert read["host_ms_per_round"] == pytest.approx(51)
    # idle 41 ms of the 100: 2 outside program spans, 2 in host syncs
    assert read["host_bound_idle_pct"] == pytest.approx(37)
    assert read["host_bound_idle_pct"] <= r.trace.idle_pct == pytest.approx(41)


@pytest.mark.parametrize("name", ["decode_ms_per_step", "prefill_pad_pct",
                                  "host_ms_per_round", "host_bound_idle_pct"])
def test_a_trace_without_program_spans_has_nothing_to_read(readings, name):
    read = harness.reader(ROOT, name + ".longdoc")
    with pytest.raises(KeyError, match="no 'serve"):
        read(readings(program=[]))
    with pytest.raises(ValueError, match="not a traced run"):
        read(types.SimpleNamespace(trace=None))
