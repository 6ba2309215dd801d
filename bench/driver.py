"""Drives one cell's traffic through a ``ServeFrontend`` on the host clock.

The mix is an offline backlog: a request is due, and is submitted, each
time the waiting line falls below ``max_slots``.  Tokens are timestamped
by the benchmark's own sink as each chunk reaches the host.

The run is one stream: a lead-in of the mix's own requests, then the
measured window, then a drain in which nothing new is submitted and the
requests due in the window finish.  The lead-in is a whole number of the
mix's periods, so the window starts on a period boundary and every run
measures the same work; it is long enough that every program the
window's rounds call was made in it (``window_compiles`` reads 0).  No
request is served only to warm up: set-up makes the programs the cell's
traffic uses, and no others.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import generator


@dataclasses.dataclass
class Record:
    index: int
    prompt: np.ndarray
    gen: int
    due: float
    rid: Optional[int] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    token_counts: List[int] = dataclasses.field(default_factory=list)
    tokens: List[np.ndarray] = dataclasses.field(default_factory=list)
    output: object = None

    @property
    def n_tokens(self) -> int:
        return int(sum(self.token_counts))

    @property
    def served(self) -> np.ndarray:
        return (np.concatenate(self.tokens).astype(np.int32) if self.tokens
                else np.zeros(0, np.int32))

    @property
    def ok(self) -> bool:
        out = self.output
        return (out is not None and out.reject_reason is None
                and out.fault_reason is None and self.n_tokens == self.gen)


@dataclasses.dataclass
class Run:
    records: List[Record]
    t0: float  # lead-in start
    w0: float
    w1: float
    slot_samples: List[tuple]  # (time, live slots) after each round
    trace_span: Optional[tuple] = None  # (t0, t1) of the traced rounds

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if self.w0 <= r.due < self.w1]


class Driver:
    """One run of a mix against one front end."""

    def __init__(self, frontend, traffic: Dict, seed: int, vocab: int,
                 clock: Callable[[], float] = time.perf_counter):
        if traffic["arrivals"]["kind"] != "backlog":
            raise ValueError(f"unknown arrivals {traffic['arrivals']['kind']!r}")
        self.fe = frontend
        self.traffic = traffic
        self.seed = seed
        self.vocab = vocab
        self.clock = clock
        self.records: List[Record] = []
        self.by_rid: Dict[int, Record] = {}
        self.slot_samples: List[tuple] = []
        self.max_slots = frontend.engine.config.max_slots

    def _submit(self) -> None:
        prompt, gen = generator.request(self.traffic, self.seed, len(self.records), self.vocab)
        rec = Record(len(self.records), prompt, gen, self.clock())
        self.records.append(rec)

        def sink(toks, rec=rec):
            with TraceAnnotation("bench.sink"):
                rec.token_times.append(self.clock())
                rec.token_counts.append(int(np.shape(toks)[-1]))
                rec.tokens.append(np.asarray(toks).reshape(-1))

        with TraceAnnotation("bench.submit"):
            rec.rid = self.fe.submit(rec.prompt, rec.gen, on_tokens=sink)
        self.by_rid[rec.rid] = rec

    def _round(self) -> None:
        with TraceAnnotation("bench.pump"):
            self.fe.pump()
        for out in self.fe.drain():
            rec = self.by_rid.get(out.request_id)
            if rec is not None:
                rec.output = out
        self.slot_samples.append((self.clock(), self.fe.engine.stats()["slots_live"]))

    def run(self, lead_in_requests: int, window_s: float, drain_s: float,
            trace: Optional["Tracer"] = None) -> Run:
        """Lead-in, window, drain.  The window opens as request
        ``lead_in_requests`` (a multiple of the mix's period) is
        submitted."""
        if lead_in_requests % self.traffic["block"]:
            raise ValueError(f"lead_in_requests={lead_in_requests} is not a whole "
                             f"number of periods of {self.traffic['block']}")
        t0 = self.clock()
        w0 = w1 = None
        span = TraceAnnotation("bench.lead_in")
        span.__enter__()
        while True:
            now = self.clock()
            if w1 is not None and now >= w1:
                break
            if trace is not None and w0 is not None:
                trace.maybe_toggle(now, w0)
            while self.fe.stats["queue_depth"] < self.max_slots:
                if w0 is None and len(self.records) == lead_in_requests:
                    span.__exit__(None, None, None)
                    w0 = self.clock()
                    w1 = w0 + window_s
                self._submit()
            self._round()
        if trace is not None:
            trace.stop()
        # drain: what was due in the window finishes; nothing new arrives
        due = [r for r in self.records if w0 <= r.due < w1]
        end = w1 + drain_s
        with TraceAnnotation("bench.drain"):
            while any(r.output is None for r in due) and self.fe.busy() and self.clock() < end:
                self._round()
        return Run(self.records, t0, w0, w1, self.slot_samples,
                   trace.span if trace is not None else None)


class Tracer:
    """Profiles ``seconds`` of the window from ``after_s`` into it,
    starting and stopping between engine rounds, so every program the
    trace holds ran to its end inside it."""

    def __init__(self, log_dir: str, after_s: float, seconds: float,
                 clock: Callable[[], float] = time.perf_counter):
        self.log_dir = log_dir
        self.after_s = after_s
        self.seconds = seconds
        self.clock = clock
        self.span: Optional[tuple] = None
        self._on = False

    def maybe_toggle(self, now: float, w0: float) -> None:
        import jax

        if not self._on and self.span is None and now >= w0 + self.after_s:
            jax.profiler.start_trace(self.log_dir)
            self._on = True
            self.span = (self.clock(), None)
        elif self._on and now >= self.span[0] + self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax

        if self._on:
            t1 = self.clock()
            jax.profiler.stop_trace()
            self._on = False
            self.span = (self.span[0], t1)
