"""gf_matmul calls per chunkset decoded in the window (program counters:
``kernels.ops.gf_traffic`` over ``ReadStats.chunksets_decoded``)."""


def read(r):
    if not r.counters.get("chunksets_decoded"):
        return None
    return r.counters["gf_calls"] / r.counters["chunksets_decoded"]
