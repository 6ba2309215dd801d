"""Engine round: device-idle time whose innermost program span is one of
the serving program's (``serve.*``, ``frontend.*``) other than
``serve.host_sync``, over the traced window.  Part of
``device_idle_pct``."""
from bench import spans


def read(r):
    s = spans.of(r)
    s.named(spans.STEP)  # a program without spans has nothing to read
    idle = s.idle_by_span()
    host = sum(v for k, v in idle.items() if k not in (spans.OUTSIDE, spans.HOST_SYNC))
    return 100.0 * host / r.trace.window_s
