"""Device: 1 - (union of device operation intervals) / traced window."""


def read(r):
    return r.trace.idle_pct
