"""Kernels: the paged decode attention kernel's share of its roofline.
The least time of the work (``bench/work.py``, from live contexts) over
the kernel's device time in the fused decode programs."""
from bench import work
from bench.names import DECODE_PROGRAM, PAGED_ATTN_KERNEL


def read(r):
    _, decode = r.traced_calls()
    ctx = [p + s + 1 for c in decode for s in range(c.steps) for p in c.positions]
    if not ctx:
        return None
    least, _ = work.least_seconds(*work.decode_attn(r.cfg, ctx), r.peak)
    return 100.0 * least / r.trace.op_seconds(PAGED_ATTN_KERNEL, DECODE_PROGRAM)
