"""One run of one cell: set-up, the measured window, the drain, the
readings, the comparison with the reference, and the result line.

``run.py`` calls :func:`run_cell` after finding the chips; tests call it
with ``require_tpu=False`` at a small size on the CPU.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import logging
import shutil
import sys
import time
from collections import Counter
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from bench import check, peaks, weights
from bench.driver import Driver, Run, Tracer
from bench.probe import Probe
from bench.spec import Cell

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found platform "
                     f"{devs[0].platform!r} ({len(devs)} device(s)). No fallback.")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def place_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, so that runs of one checkout share it
    and two checkouts share nothing; every program is cached."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog(logging.Handler):
    """When programs were made: each jit cache miss that lowers a program
    (JAX's ``Compiling <name> ...`` record, logged at debug level),
    backend compiles and persistent-cache hits (JAX's monitoring
    events)."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self, clock: Callable[[], float]):
        import jax

        super().__init__(logging.DEBUG)
        self.lowered: List[Tuple[float, str]] = []  # (time, program name)
        self.times: List[float] = []  # backend compiles
        self.loads: List[float] = []  # persistent-cache hits
        self.seconds = 0.0
        self.clock = clock
        log = logging.getLogger(self.LOGGER)
        self._saved = (log.level, log.propagate)
        log.setLevel(logging.DEBUG)
        log.propagate = False  # the records are read here, not printed
        log.addHandler(self)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def emit(self, record: logging.LogRecord) -> None:
        if record.msg.startswith("Compiling ") and record.args:
            self.lowered.append((self.clock(), str(record.args[0])))

    def close(self) -> None:
        log = logging.getLogger(self.LOGGER)
        log.removeHandler(self)
        log.setLevel(self._saved[0])
        log.propagate = self._saved[1]
        super().close()

    def _on_duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.times.append(self.clock())
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.loads.append(self.clock())

    def between(self, t0: float, t1: float) -> List[str]:
        """Names of the programs made in ``[t0, t1)``."""
        return [name for t, name in self.lowered if t0 <= t < t1]


@dataclasses.dataclass
class Readings:
    """What the metric readers read."""
    cell: Cell
    cfg: Dict
    run: Run
    probe: Probe
    setup_s: float
    compiles: CompileLog
    max_slots: int
    device_kind: str
    n_devices: int
    trace: object = None  # trace_reduce.Reduced in a traced run

    @property
    def peak(self) -> Dict[str, float]:
        return peaks.peaks(self.device_kind)

    def traced_calls(self):
        """The probe's (prefill, decode) calls inside the traced window."""
        t0, t1 = self.run.trace_span
        return self.probe.between(t0, t1)


def reader(root: Path, name: str):
    """``bench/metrics/<quantity>.py`` for ``<quantity>[.<suffix>]``."""
    base = name.split(".")[0]
    path = root / "bench" / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(r: Readings, metrics: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        try:
            v = reader(r.cell.root, m["name"])(r)
        except (KeyError, ValueError, ZeroDivisionError) as e:
            print(f"bench: {m['name']}: nothing to read ({type(e).__name__}: {e})",
                  file=sys.stderr)
            v = None
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def memory_peak(devs) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def compare(cell: Cell, seed: int, run: Run,
            control: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each with its limit: the cell's gap readings
    (at most their limit) and the served tokens compared (at least
    ``min_tokens``).  With ``control``, the gaps are those of that
    control's first choices at the same positions (``bench/control.py``)."""
    ck = cell.workload["check"]
    recs = check.sample(run.due_in_window(), seed, ck["min_tokens"], ck["max_requests"])
    numbers = {}
    if recs:
        w = weights.make(cell.family, cell.config, seed)
        items = [(r.prompt, r.served) for r in recs]
        cols = {}
        g = check.gaps(cell.family, cell.config, w, items, ck["seq_len"], ck["gen_len"],
                       ck["batch"], control, cols)
        del w
        read = check.readings(cell.family, g, cols)
        numbers = {name: {"value": read[name], "limit": lim} for name, lim in ck["limits"].items()}
    numbers["tokens_compared"] = {"value": sum(len(r.served) for r in recs),
                                  "limit": ck["min_tokens"]}
    return numbers


def correct_of(numbers: Dict[str, Dict[str, float]]) -> bool:
    gaps = [v for k, v in numbers.items() if k != "tokens_compared"]
    n = numbers["tokens_compared"]
    return bool(gaps) and all(v["value"] <= v["limit"] for v in gaps) and n["value"] >= n["limit"]


def serve(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
          require_tpu: bool = True, arch_cfg=None, build=None) -> Tuple[Dict, Run]:
    """Set-up, window and drain; the readings and the result line without
    its comparison, and what was served.  The program's state is freed
    before it returns."""
    from bench import system, trace_reduce

    devs = devices(cell.chips, require_tpu)
    compiles = CompileLog(time.perf_counter)
    try:
        head, run = _measure(cell, seed, seconds, trace, t_start, devs, compiles,
                             arch_cfg, build or system.build, trace_reduce)
    finally:
        compiles.close()
    # the program's state is gone with _measure's frame; the reference runs next
    gc.collect()
    return head, run


def _measure(cell, seed, seconds, trace, t_start, devs, compiles, arch_cfg, build,
             trace_reduce) -> Tuple[Dict, Run]:
    clock = compiles.clock
    wl = cell.workload
    t_build = clock()
    params, engine, fe = build(cell, seed, arch_cfg)
    print(f"bench: devices at {t_build - t_start:.1f} s, weights and engine built in "
          f"{clock() - t_build:.1f} s", file=sys.stderr)
    probe = Probe(engine, clock)
    driver = Driver(fe, cell.traffic, seed, cell.config["vocab_size"], clock)
    peak_built = memory_peak(devs)
    tracer = None
    log_dir = str(cell.root / ".bench_trace" / cell.name)
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        tracer = Tracer(log_dir, wl["trace"]["after_s"], wl["trace"]["seconds"], clock)
    # set-up ends where the window starts: process start to lead-in end
    run = driver.run(wl["lead_in_requests"], seconds, wl["drain_s"], tracer)
    setup_s = run.w0 - t_start
    peak_bytes = memory_peak(devs)
    max_slots = engine.config.max_slots
    r = Readings(cell, cell.config, run, probe, setup_s, compiles, max_slots,
                 devs[0].device_kind, len(devs))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result: Dict = {}
    if trace:
        r.trace = trace_reduce.reduce_dir(log_dir)
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s
        metrics = read_metrics(r, cell.per_layer)
        result["breakdown"] = {"device_ops": r.trace.top_ops(10),
                               "idle_gaps": r.trace.idle_gaps(10)}
    else:
        metrics = read_metrics(r, cell.end_to_end)
    due = run.due_in_window()
    print(f"bench: {len(due)} requests due in the window; lead-in {run.w0 - run.t0:.1f} s "
          f"({wl['lead_in_requests']} requests); {len(compiles.times)} compiles "
          f"({compiles.seconds:.1f} s) and {len(compiles.loads)} cache loads in all; programs "
          f"made in the window: {dict(Counter(compiles.between(run.w0, run.w1)))}; set-up "
          f"{setup_s:.1f} s; {len(probe.prefill)} prefill and {len(probe.decode)} decode "
          f"dispatches; peak bytes in use {peak_built} with weights and engine built, "
          f"{peak_bytes} after the window", file=sys.stderr)
    head = {"attempted": len(due), "failed": sum(not rec.ok for rec in due),
            "metrics": metrics, "device": device}
    head.update(result)
    return head, run


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, arch_cfg=None, build=None) -> Dict:
    """One whole run: its result line, ``check`` last."""
    head, run = serve(cell, seed, seconds, trace, t_start, require_tpu, arch_cfg, build)
    t_ref = time.perf_counter()
    numbers = compare(cell, seed, run)
    print(f"bench: comparison with the reference took {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    return {"correct": correct_of(numbers), **head, "check": numbers}


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    import json

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.spec import ROOT, load_cell

    cell = load_cell(args.workload, ROOT)
    place_cache(ROOT)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, v in res["check"].items():
        print(f"check {name}: {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0
