"""Engine round: mean host milliseconds of an engine round in the traced
window, a ``serve.step`` span less the ``serve.host_sync`` spans inside
it: the calls that wait for the device (reading a result, and the
prefill and decode dispatches, which wait for device memory while the
program before them runs)."""
from bench import spans


def read(r):
    rounds = spans.of(r).round_host_ms()
    return sum(rounds) / len(rounds)
