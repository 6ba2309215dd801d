"""Programs: programs made inside the window, each jit cache miss that
lowers one, whether it then compiles or loads from the persistent cache.
Should be 0."""


def read(r):
    return float(len(r.compiles.between(r.run.w0, r.run.w1)))
