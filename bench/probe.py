"""Benchmark-side spans and counters around the engine's calls into its
programs.

The engine exposes no per-dispatch record, so the probe wraps the two
calls that dispatch device work, as instance attributes of one engine:
the chunked paged prefill (``_prefill_chunk_paged``: which slots, how
many prompt tokens, from which position) and the fused decode
(``_fused``: how many steps, at which positions).  Each wrapped call
also opens a ``TraceAnnotation``, so a profiler trace shows what the host
was doing.  Where the engine no longer has these attributes the probe
records nothing, and the per-layer metrics that read it are left out of
the result line.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Callable, List

from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class PrefillCall:
    t0: float
    t1: float
    takes: tuple    # prompt tokens run per row
    starts: tuple   # absolute position of each row's first token


@dataclasses.dataclass(frozen=True)
class DecodeCall:
    t0: float
    t1: float
    steps: int
    positions: tuple  # position of the fed token, per decoding slot


class Probe:
    def __init__(self, engine, clock: Callable[[], float]):
        self.prefill: List[PrefillCall] = []
        self.decode: List[DecodeCall] = []
        self.clock = clock
        try:
            self._install(engine)
            self.ok = True
        except AttributeError as e:
            print(f"bench: probe not installed ({e}); its metrics are left out",
                  file=sys.stderr)
            self.ok = False

    def _install(self, engine):
        prefill, fused, _ = engine._prefill_chunk_paged, engine._fused, engine._slots

        def prefill_chunk(plan):
            starts = tuple(engine._slots[i].filled for i, _ in plan)
            t0 = self.clock()
            with TraceAnnotation("bench.prefill_dispatch"):
                out = prefill(plan)
            self.prefill.append(PrefillCall(t0, self.clock(),
                                            tuple(t for _, t in plan), starts))
            return out

        def decode(*args, steps, **kwargs):
            pos = tuple(s.pos for s in engine._slots
                        if s is not None and s.state.name == "DECODING")
            t0 = self.clock()
            with TraceAnnotation("bench.decode_dispatch"):
                out = fused(*args, steps=steps, **kwargs)
            self.decode.append(DecodeCall(t0, self.clock(), steps, pos))
            return out

        engine._prefill_chunk_paged = prefill_chunk
        engine._fused = decode

    def between(self, t0: float, t1: float):
        """Calls dispatched in ``[t0, t1)``: (prefill, decode)."""
        return ([c for c in self.prefill if t0 <= c.t0 < t1],
                [c for c in self.decode if t0 <= c.t0 < t1])
