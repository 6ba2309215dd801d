"""Scheduler: mean share of the engine's slots holding a request
(``ServeEngine.stats()["slots_live"]``), sampled after each round in the
window."""


def read(r):
    run = r.run
    vals = [n for t, n in run.slot_samples if run.w0 <= t < run.w1]
    return 100.0 * sum(vals) / len(vals) / r.max_slots if vals else None
