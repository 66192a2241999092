"""Device busy time per chunkset put in the window, in ms (trace busy union)."""


def read(r):
    if r.trace is None or not r.chunksets:
        return None
    return r.trace.busy_s * 1e3 / r.chunksets
