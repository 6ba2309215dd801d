"""The reduction from a profiler trace to per-layer numbers, on synthetic
traces: overlaps count once, names that match nothing raise, gaps are
labelled by the host span they fall in, and the peaks table refuses a
device it does not know."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import peaks  # noqa: E402
from bench.trace_reduce import Event, Line, Plane, Reduced, union  # noqa: E402

MS = 1e6  # ns


def ev(name, start_ms, dur_ms, **stats):
    return Event(name, start_ms * MS, dur_ms * MS, tuple(stats.items()))


def trace(ops, modules, spans, devices=1):
    planes = [Plane("/host:CPU", (Line("python", tuple(spans)),))]
    for d in range(devices):
        planes.append(Plane(f"/device:TPU:{d}", (
            Line("XLA Modules", tuple(modules)), Line("XLA Ops", tuple(ops)))))
    return planes


SPANS = [ev("bench.pump", 0, 40), ev("bench.decode_dispatch", 1, 2),
         ev("bench.pump", 50, 50), ev("bench.sink", 95, 3)]
OPS = [
    ev("%while.2 = (s32[]) while(...)", 2, 13),  # holds the two below
    ev("%fusion.1 = bf16[8] fusion(...)", 2, 10),
    ev("%paged_attention_kernel.3 = (f32[3,32,1,64]) custom-call(...)", 5, 10),
    ev("%fusion.9 = bf16[8] fusion(...)", 60, 20),
    ev("%paged_attention_kernel.4 = (f32[2,32,512,64]) custom-call(...)", 85, 5),
    ev("%late = f32[] copy(...)", 99, 10),  # runs past the window: clipped to it
]
MODULES = [ev("jit_fused(1789340851046412144)", 2, 13), ev("jit_f(868403349089)", 60, 30)]


def test_overlapping_events_count_once():
    r = Reduced(trace(OPS, MODULES, SPANS))
    assert r.window_s == pytest.approx(0.100)
    # [2,15] + [60,80] + [85,90] + [99,100] = 13 + 20 + 5 + 1 ms
    assert r.busy_s == pytest.approx(0.039)
    assert r.idle_pct == pytest.approx(61.0)
    assert union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_busy_is_averaged_over_devices_in_use():
    r = Reduced(trace(OPS, MODULES, SPANS, devices=2))
    assert r.busy_s == pytest.approx(0.039)


def test_program_and_kernel_time_by_stable_name():
    r = Reduced(trace(OPS, MODULES, SPANS))
    assert r.program_seconds(r"^jit_fused$") == pytest.approx(0.013)
    assert r.program_seconds(r"^jit_f$") == pytest.approx(0.030)
    assert r.op_seconds("paged_attention_kernel") == pytest.approx(0.015)
    assert r.op_seconds("paged_attention_kernel", r"^jit_fused$") == pytest.approx(0.010)
    assert r.op_seconds("paged_attention_kernel", r"^jit_f$") == pytest.approx(0.005)


def test_unknown_names_raise():
    r = Reduced(trace(OPS, MODULES, SPANS))
    with pytest.raises(KeyError, match="no program"):
        r.program_seconds(r"^jit_renamed$")
    with pytest.raises(KeyError, match="no device operation"):
        r.op_seconds("flash_decode_kernel")
    with pytest.raises(KeyError):
        r.op_seconds("paged_attention_kernel", r"^jit_other$")
    # an operation outside every program, clipped to the window
    assert ["/late", pytest.approx(0.001)] in r.top_ops(10)


def test_trace_without_device_or_window_is_refused():
    with pytest.raises(ValueError, match="no TPU"):
        Reduced([Plane("/host:CPU", (Line("python", tuple(SPANS)),))])
    with pytest.raises(ValueError, match="bench.pump"):
        Reduced(trace(OPS, MODULES, [ev("bench.sink", 0, 1)]))


def test_idle_gaps_labelled_by_host_span():
    r = Reduced(trace(OPS, MODULES, SPANS))
    gaps = dict((name, s) for name, s in r.idle_gaps())
    # gaps cut at span edges: [0,1] pump, [1,2] dispatch, [15,40] pump,
    # [40,50] outside, [50,60] pump, [80,85] pump, [90,95] pump,
    # [95,98] sink, [98,99] pump
    assert gaps["bench.decode_dispatch"] == pytest.approx(0.001)
    assert gaps["bench.pump"] == pytest.approx(0.001 + 0.025 + 0.010 + 0.005 + 0.005 + 0.001)
    assert gaps["bench.sink"] == pytest.approx(0.003)
    assert gaps["outside bench spans"] == pytest.approx(0.010)
    top = r.top_ops(3)
    assert top[0] == ["jit_f/fusion.9", pytest.approx(0.020)]
    assert top[1][0] in ("jit_fused/fusion.1", "jit_fused/paged_attention_kernel.3")
    assert not any(name.endswith("while.2") for name, _ in r.top_ops(10))


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
