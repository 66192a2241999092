"""Window time not covered by any device operation, per chunkset decoded, in ms.

The host read path (fleet routing, fetch with Merkle verification, the Clay
plane schedule, range extraction) runs in these gaps; it is not split yet.
"""


def read(r):
    if r.trace is None or not r.chunksets:
        return None
    return (r.trace.window_s - r.trace.busy_s) * 1e3 / r.chunksets
