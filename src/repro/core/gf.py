"""GF(2^8) arithmetic for erasure coding.

Three execution paths share one semantic:

* ``numpy`` path (``mul``, ``matmul_np``…)  — used by the coordination layer
  and by small setup-time linear algebra (matrix inversion for decode plans).
* ``jnp`` path (``mul_jnp``, ``matmul_jnp``) — pure-jnp oracle used as the
  Pallas kernel reference and for small on-device coding.
* Pallas kernel (``repro.kernels.gf_matmul``) — the bulk data-path encoder /
  decoder; validated against ``matmul_jnp``.

Field: GF(2^8) with the AES-adjacent polynomial x^8+x^4+x^3+x^2+1 (0x11D),
the standard choice of ISA-L / jerasure / Ceph's clay plugin.
"""
from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1 (primitive)
GENERATOR = 2


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]  # wraparound so exp[(la+lb)] needs no mod
    return exp, log


EXP_TABLE, LOG_TABLE = _build_tables()


# ---------------------------------------------------------------------------
# numpy path
# ---------------------------------------------------------------------------
def mul(a, b):
    """Element-wise GF(2^8) multiply on uint8 numpy arrays (broadcasting)."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    out = EXP_TABLE[LOG_TABLE[a] + LOG_TABLE[b]]
    return np.where((a == 0) | (b == 0), np.uint8(0), out)


def inv(a):
    """Multiplicative inverse (a must be nonzero)."""
    a = np.asarray(a, dtype=np.uint8)
    if np.any(a == 0):
        raise ZeroDivisionError("gf.inv(0)")
    return EXP_TABLE[255 - LOG_TABLE[a]]


def div(a, b):
    return mul(a, inv(b))


def pow_(a: int, e: int) -> int:
    if e == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP_TABLE[(int(LOG_TABLE[a]) * e) % 255])


def matmul_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """GF(2^8) matrix product: (M,K) x (K,N) -> (M,N), uint8."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[0]
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for k in range(a.shape[1]):
        col = a[:, k : k + 1]  # (M,1)
        if not col.any():
            continue
        out ^= mul(col, b[k : k + 1, :])
    return out


def mat_inv(a: np.ndarray) -> np.ndarray:
    """Invert a square GF(2^8) matrix by Gauss-Jordan elimination."""
    a = np.array(a, dtype=np.uint8)
    n = a.shape[0]
    assert a.shape == (n, n)
    aug = np.concatenate([a, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = None
        for r in range(col, n):
            if aug[r, col] != 0:
                piv = r
                break
        if piv is None:
            raise np.linalg.LinAlgError("singular GF(256) matrix")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        aug[col] = mul(aug[col], inv(aug[col, col]))
        for r in range(n):
            if r != col and aug[r, col] != 0:
                aug[r] ^= mul(aug[r, col], aug[col])
    return aug[:, n:]


def solve(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Solve a @ x = b over GF(2^8) (a square, invertible)."""
    return matmul_np(mat_inv(a), b)


def field_points(count: int) -> np.ndarray:
    """The first ``count`` points of the field in the codes' order: 1, 2, ...,
    255 and then 0, so a code of length 256 uses every element once."""
    if not 0 < count <= 256:
        raise ValueError(f"GF(2^8) has 256 points, not {count}")
    return (np.arange(1, count + 1) % 256).astype(np.uint8)


def vandermonde(rows: int, cols: int, points: np.ndarray | None = None) -> np.ndarray:
    """Vandermonde matrix V[i,j] = points[j]^i (0^0 = 1) on distinct points,
    by default :func:`field_points`; any `rows` columns of a row-prefix form a
    square Vandermonde matrix, which is invertible, so it serves as an MDS
    parity-check."""
    if points is None:
        points = field_points(cols)
    points = np.asarray(points, dtype=np.uint8)
    assert len(points) == cols and len(np.unique(points)) == cols
    v = np.zeros((rows, cols), dtype=np.uint8)
    v[0, :] = 1
    for i in range(1, rows):
        v[i] = mul(v[i - 1], points)
    return v


# ---------------------------------------------------------------------------
# jnp path (oracle for the Pallas kernel; carry-less multiply, no tables)
# ---------------------------------------------------------------------------
@functools.lru_cache(None)
def _jnp():
    import jax.numpy as jnp

    return jnp


def mul_jnp(a, b):
    """Branchless GF(2^8) multiply: 8-step shift/xor (Russian peasant).

    Operates on int32 arrays holding byte values; mirrors exactly what the
    Pallas kernel does on the VPU (no gathers/tables).
    """
    jnp = _jnp()
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    acc = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), jnp.int32)
    for _ in range(8):
        acc = acc ^ (jnp.where((a & 1) != 0, b, 0))
        a = a >> 1
        carry = (b & 0x80) != 0
        b = (b << 1) & 0xFF
        b = jnp.where(carry, b ^ (POLY & 0xFF), b)
    return acc


def matmul_jnp(a, b):
    """GF(2^8) matmul on int-valued jnp arrays: (M,K) x (K,N) -> (M,N)."""
    jnp = _jnp()
    prod = mul_jnp(a[:, :, None], b[None, :, :])  # (M,K,N)
    out = prod[:, 0, :]
    for k in range(1, prod.shape[1]):
        out = out ^ prod[:, k, :]
    return out
