"""Programs: the share of the chunked prefill grids' token rows that are
padding, over the engine's ``serve.prefill_chunk`` spans in the traced
window: 1 - (real prompt tokens) / (rows x pow2 width)."""
from bench import spans


def read(r):
    s = spans.of(r)
    chunks = [e for e in s.named(spans.PREFILL_CHUNK) if e.stat("width") is not None]
    grid = sum(float(e.stat("rows")) * float(e.stat("width")) for e in chunks)
    return 100.0 * (1.0 - s.total(spans.PREFILL_CHUNK, "tokens") / grid)
