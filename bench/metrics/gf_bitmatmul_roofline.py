"""gf_bitmatmul's share of its HBM roofline in the window, in %.

The least time the chip could take for the calls the bit-matrix kernel
served is their algorithm bytes (``gf_matmul_work``) over the chip's HBM
bandwidth; the time they took is the device time of the jitted
``gf_bitmatmul`` programs in the trace.  The calls are picked from the
window's kernel shapes by the program's own rule
(``kernels.ops.uses_bit_matrix``), so shapes the other kernel served are
never counted.  Nothing to read (no trace, or no such program, as in a
program without that kernel): None.
"""
from bench.metrics.gf_matmul_work import algorithm_bytes


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.program_seconds("gf_bitmatmul")
    if seconds <= 0:
        return None
    from repro.kernels import ops

    served = [shape for shape in r.kernel_shapes if ops.uses_bit_matrix(*shape[:2])]
    if not served:
        return None
    least = sum(algorithm_bytes(*shape) for shape in served) / r.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
