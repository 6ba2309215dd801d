"""Programs: device milliseconds of the chunked paged prefill programs in
the traced window, per thousand prompt tokens they prefilled."""
from bench.names import PREFILL_PROGRAM


def read(r):
    prefill, _ = r.traced_calls()
    tokens = sum(sum(c.takes) for c in prefill)
    if not tokens:
        return None
    return 1e3 * r.trace.program_seconds(PREFILL_PROGRAM) / (tokens / 1e3)
