"""Process start to window start: imports, weights, engine, compiles or
cache loads, and the lead-in."""


def read(r):
    return r.setup_s
