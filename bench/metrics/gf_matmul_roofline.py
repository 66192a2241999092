"""gf_matmul's share of its HBM roofline in the window, in %.

The least time the chip could take for the window's gf_matmul calls is
their algorithm bytes (``gf_matmul_work``) over the chip's HBM bandwidth;
the time they took is the device time of the jitted ``gf_matmul`` programs
in the trace, padding and slicing included.  Nothing to read: None.
"""
from bench.metrics.gf_matmul_work import algorithm_bytes


def read(r):
    if r.trace is None or not r.kernel_shapes:
        return None
    seconds = r.trace.program_seconds("gf_matmul")
    if seconds <= 0:
        return None
    least = sum(algorithm_bytes(*shape) for shape in r.kernel_shapes) / r.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
