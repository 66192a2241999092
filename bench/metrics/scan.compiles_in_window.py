"""XLA compiles (persistent-cache loads included) inside the window; should be 0."""


def read(r):
    return r.counters.get("compiles")
