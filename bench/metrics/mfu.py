"""Whole step: model FLOPs of the tokens processed in the traced window
(prompt tokens prefilled, tokens decoded; ``bench/work.py``) over the
traced window times the chips' bf16 peak."""
from bench import work


def read(r):
    prefill, decode = r.traced_calls()
    ctx = [s + i + 1 for c in prefill for s, n in zip(c.starts, c.takes) for i in range(n)]
    ctx += [p + s + 1 for c in decode for s in range(c.steps) for p in c.positions]
    if not ctx:
        return None
    peak = r.peak["bf16_flops"] * r.n_devices
    return 100.0 * work.model_flops(r.cell.family, r.cfg, ctx) / (r.trace.window_s * peak)
