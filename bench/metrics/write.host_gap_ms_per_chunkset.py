"""Window time not covered by any device operation, per chunkset put, in ms.

The host write path (``ShelbyClient.prepare``: Clay encode and chunk
commitments; ``RPCNode.write_blob``: verification and dispersal) runs in
these gaps.
"""


def read(r):
    if r.trace is None or not r.chunksets:
        return None
    return (r.trace.window_s - r.trace.busy_s) * 1e3 / r.chunksets
