"""The work of one GF(2^8) matmul, from its shapes alone.

C (M, N) = A (M, K) (x) B (K, N) over uint8 has to read A and B and write C
once: M*K + K*N + M*N bytes, whatever implements it.  No credit is given
for int32 lanes, padding to the kernel's block, or the xtime steps.
"""


def algorithm_bytes(m: int, k: int, n: int) -> int:
    return m * k + k * n + m * n
