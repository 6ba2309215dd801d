"""Kernels: the paged prefill attention kernel's share of its roofline.
The least time of the work (``bench/work.py``: causal attention over the
real prompt tokens and their contexts) over the kernel's device time in
the chunked prefill programs."""
from bench import work
from bench.names import PAGED_ATTN_KERNEL, PREFILL_PROGRAM


def read(r):
    prefill, _ = r.traced_calls()
    rows = [(s, n) for c in prefill for s, n in zip(c.starts, c.takes)]
    if not rows:
        return None
    least, _ = work.least_seconds(*work.prefill_attn(r.cfg, rows), r.peak)
    return 100.0 * least / r.trace.op_seconds(PAGED_ATTN_KERNEL, PREFILL_PROGRAM)
