"""Systematic MDS base code over GF(2^8) with a Vandermonde parity check.

This is both (a) the Reed-Solomon baseline that the paper compares Clay codes
against (repair bandwidth benchmark) and (b) the per-plane base code of the
coupled-layer (Clay) construction in ``clay.py``.

Code definition: an ``[n, k]`` code with ``m = n - k`` parity symbols and a
parity-check matrix ``H`` (m x n).  A vector ``c`` (length n, per byte column)
is a codeword iff ``H @ c = 0`` over GF(2^8).  ``H`` is Vandermonde on distinct
points (``gf.field_points``: 1..255, then 0, so n may reach 256), so every
``m x m`` column submatrix (of the full row set) is invertible -> the code is
MDS: any ``k`` symbols determine the rest.

The *data path* (multiplying a small decode/encode matrix into wide byte
arrays) is delegated to ``repro.kernels.gf_matmul`` (Pallas) or to the pure
numpy path — selectable so the coordination layer never needs a device.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core import gf


@dataclasses.dataclass(frozen=True)
class MDSCode:
    n: int
    k: int

    @property
    def m(self) -> int:
        return self.n - self.k

    @functools.cached_property
    def parity_check(self) -> np.ndarray:
        """H: (m, n) Vandermonde parity-check matrix."""
        return gf.vandermonde(self.m, self.n)

    # -- encode -------------------------------------------------------------
    @functools.cached_property
    def encode_matrix(self) -> np.ndarray:
        """(m, k) matrix P with parity = P @ data (systematic encoding).

        From H = [Hd | Hp] (split at k): Hd @ d + Hp @ p = 0
        -> p = inv(Hp) @ Hd @ d.
        """
        h = self.parity_check
        hd, hp = h[:, : self.k], h[:, self.k :]
        return gf.matmul_np(gf.mat_inv(hp), hd)

    def encode(self, data: np.ndarray, matmul=gf.matmul_np) -> np.ndarray:
        """data: (k, nbytes) -> codeword (n, nbytes), systematic."""
        data = np.asarray(data, dtype=np.uint8)
        assert data.shape[0] == self.k, (data.shape, self.k)
        parity = matmul(self.encode_matrix, data)
        return np.concatenate([data, np.asarray(parity, np.uint8)], axis=0)

    # -- erasure decode -----------------------------------------------------
    def decode_matrix(self, known: tuple[int, ...]) -> tuple[np.ndarray, tuple[int, ...]]:
        """Solve for the erased symbols from any >= k known symbols.

        Returns (R, erased) with erased values = R @ known_values, where
        ``known`` lists the available symbol indices (uses the first k).
        """
        known = tuple(sorted(known))[: self.k]
        if len(known) < self.k:
            raise ValueError(f"need >= k={self.k} known symbols, got {len(known)}")
        erased = tuple(i for i in range(self.n) if i not in set(known))
        e = len(erased)
        if e == 0:
            return np.zeros((0, self.k), np.uint8), erased
        h = self.parity_check[:e, :]  # e rows suffice (row-prefix Vandermonde)
        he = h[:, list(erased)]  # (e, e) invertible (MDS)
        hk = h[:, list(known)]  # (e, k)
        r = gf.matmul_np(gf.mat_inv(he), hk)  # (e, k)
        return r, erased

    def decode(
        self,
        shards: dict[int, np.ndarray],
        matmul=gf.matmul_np,
    ) -> np.ndarray:
        """Reconstruct full codeword (n, nbytes) from any k of n shards."""
        known = tuple(sorted(shards))[: self.k]
        r, erased = self.decode_matrix(known)
        nbytes = next(iter(shards.values())).shape[-1]
        out = np.zeros((self.n, nbytes), dtype=np.uint8)
        for i in known:
            out[i] = shards[i]
        if erased:
            stacked = np.stack([shards[i] for i in known], axis=0)
            rec = np.asarray(matmul(r, stacked), np.uint8)
            for row, i in enumerate(erased):
                out[i] = rec[row]
        return out

    def reconstruct_data(self, shards: dict[int, np.ndarray], matmul=gf.matmul_np) -> np.ndarray:
        return self.decode(shards, matmul=matmul)[: self.k]

    # -- repair (RS has no better option than full decode) -------------------
    def repair_bandwidth_bytes(self, shard_bytes: int) -> int:
        """Bytes read from helpers to repair ONE lost shard (= k full shards)."""
        return self.k * shard_bytes
