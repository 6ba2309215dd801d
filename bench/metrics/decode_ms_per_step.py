"""Programs: device milliseconds of the fused decode programs in the
traced window, per decode step dispatched in it (``steps`` summed over
the engine's ``serve.decode_dispatch`` spans)."""
from bench import spans
from bench.names import DECODE_PROGRAM


def read(r):
    steps = spans.of(r).total(spans.DECODE_DISPATCH, "steps")
    return 1e3 * r.trace.program_seconds(DECODE_PROGRAM) / steps
