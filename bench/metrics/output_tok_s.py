"""Every output token that reached the host in the window, over the
window's seconds."""


def read(r):
    run = r.run
    n = sum(c for rec in run.records for t, c in zip(rec.token_times, rec.token_counts)
            if run.w0 <= t < run.w1)
    return n / (run.w1 - run.w0) if n else None
