"""A plain reference of the DAS square: GF(2^8), the code, the 2-D extension, the roots.

Written apart from the program's ``gf``, ``rs`` and ``extend2d`` (it imports
only numpy and hashlib), so that each can be checked against the other.
Every answer is exact.

* The field: GF(2^8) modulo x^8 + x^4 + x^3 + x^2 + 1 (0x11D), by a
  256 x 256 product table built from powers of the generator 2.
* The code: the systematic [n, k] code whose parity-check matrix H (m x n,
  m = n - k) is Vandermonde, ``H[i, j] = x_j ** i``, on the points
  ``x_j`` = 1, 2, ..., 255 and then 0, in that order (:func:`points`).  With
  ``H = [Hd | Hp]`` split at k, a codeword ``[d | p]`` has ``Hd d + Hp p = 0``,
  so the parity is ``p = Hp^-1 Hd d`` (characteristic 2: minus is plus),
  ``Hp^-1`` by Gauss-Jordan elimination.
* The extension of a (k, k, S) data square to (2k, 2k, S): each of the k
  data columns is extended to 2k shares (parity rows k..2k-1), then each of
  the 2k rows to 2k shares (parity columns k..2k-1), share bytes position by
  position.
* The roots, in ``core/commitments.py``'s format: a leaf is
  ``SHA-256(0x00 || bytes)``, a node ``SHA-256(0x01 || left || right)``; the
  leaves are padded to a power of two by repeating the last leaf hash.  Row
  r's tree has the shares (r, 0..2k-1) as leaves, column c's the shares
  (0..2k-1, c); the DAS root is the root of a tree whose 4k leaves are the
  2k row roots and then the 2k column roots, each hashed as a leaf.
"""
from __future__ import annotations

import hashlib

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(255, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    return exp, log


def _product_table() -> np.ndarray:
    exp, log = _tables()
    a = np.arange(256)
    table = exp[(log[a][:, None] + log[a][None, :]) % 255].astype(np.uint8)
    table[0, :] = 0
    table[:, 0] = 0
    return table


MUL = _product_table()  # MUL[a, b] = a * b
INV = np.array([0] + [int(np.flatnonzero(MUL[a] == 1)[0]) for a in range(1, 256)], np.uint8)


def matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(M, K) x (K, N) -> (M, N) over GF(2^8), one table gather per column of A."""
    a = np.asarray(a, np.uint8)
    b = np.asarray(b, np.uint8)
    out = np.zeros((a.shape[0], b.shape[1]), np.uint8)
    for j in range(a.shape[1]):
        out ^= np.take(MUL[a[:, j]], b[j], axis=1)
    return out


def inverse(a: np.ndarray) -> np.ndarray:
    """The inverse of a square GF(2^8) matrix, by Gauss-Jordan elimination."""
    n = a.shape[0]
    aug = np.concatenate([np.asarray(a, np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = col + int(np.flatnonzero(aug[col:, col])[0])  # IndexError: singular
        aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = MUL[INV[aug[col, col]], aug[col]]
        for r in np.flatnonzero(aug[:, col]):
            if r != col:
                aug[r] ^= MUL[aug[r, col], aug[col]]
    return aug[:, n:]


def points(n: int) -> np.ndarray:
    """The n evaluation points: 1..255, then 0 (n <= 256)."""
    if not 0 < n <= 256:
        raise ValueError(f"a code over GF(2^8) has at most 256 points, not {n}")
    return np.array([(j + 1) % 256 for j in range(n)], np.uint8)


def parity_check(n: int, k: int) -> np.ndarray:
    """H (n - k, n): H[i, j] = points(n)[j] ** i."""
    x = points(n)
    h = np.zeros((n - k, n), np.uint8)
    h[0] = 1
    for i in range(1, n - k):
        h[i] = MUL[h[i - 1], x]
    return h


def parity_matrix(n: int, k: int) -> np.ndarray:
    """P (n - k, k) with parity = P data: Hp^-1 Hd."""
    h = parity_check(n, k)
    return matmul(inverse(h[:, k:]), h[:, :k])


def encode(data: np.ndarray, n: int) -> np.ndarray:
    """(k, B) data symbols -> (n, B) codeword, systematic."""
    data = np.asarray(data, np.uint8)
    return np.concatenate([data, matmul(parity_matrix(n, data.shape[0]), data)], axis=0)


def extend(square: np.ndarray) -> np.ndarray:
    """(k, k, S) data square -> (2k, 2k, S): columns first, then rows."""
    k, _, s = square.shape
    p = parity_matrix(2 * k, k)
    cols = np.concatenate([square, matmul(p, square.reshape(k, k * s)).reshape(k, k, s)])
    by_col = cols.transpose(1, 0, 2)  # (k data columns, 2k rows, S)
    right = matmul(p, by_col.reshape(k, 2 * k * s)).reshape(k, 2 * k, s)
    return np.ascontiguousarray(np.concatenate([by_col, right]).transpose(1, 0, 2))


def _h(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def merkle_root(leaves: list[bytes]) -> bytes:
    level = [_h(b"\x00" + x) for x in leaves]
    while len(level) & (len(level) - 1):
        level.append(level[-1])
    while len(level) > 1:
        level = [_h(b"\x01" + level[i] + level[i + 1]) for i in range(0, len(level), 2)]
    return level[0]


def square_roots(ext: np.ndarray) -> tuple[list[bytes], list[bytes], bytes]:
    """Row roots, column roots and the DAS root of a (2k, 2k, S) square."""
    side = ext.shape[0]
    rows = [merkle_root([ext[r, c].tobytes() for c in range(side)]) for r in range(side)]
    cols = [merkle_root([ext[r, c].tobytes() for r in range(side)]) for c in range(side)]
    return rows, cols, merkle_root(rows + cols)


def path_root(leaf: bytes, index: int, path) -> bytes:
    """The root a Merkle path (sibling hashes, leaf to root) leads to from ``leaf``."""
    node = _h(b"\x00" + leaf)
    for sibling in path:
        node = _h(b"\x01" + node + sibling) if index % 2 == 0 else _h(b"\x01" + sibling + node)
        index //= 2
    return node
