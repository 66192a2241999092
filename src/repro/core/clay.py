"""Clay (Coupled-LAYer) codes — the paper's storage code (§3.3).

Faithful implementation of the construction of Vajha et al., FAST'18 (paper
ref [20]): an ``(n = k+m, k, d = n-1)`` MSR+MDS code obtained by coupling
``alpha = q^t`` layers of an ``[N, N-m]`` scalar MDS base code, where

    q = d - k + 1 = m,      t = ceil(n / q),      N = q * t,

with ``s = N - n`` *shortened* (virtual, all-zero) nodes when q does not
divide n.  Every node is a point ``(x, y)`` on a q x t grid; every sub-chunk
of a node is indexed by ``z in [q]^t``; vertex ``(x, y, z)`` is *unpaired*
("diagonal") iff ``z_y == x`` and otherwise is coupled with its partner
``(z_y, y, z(y -> x))`` through the invertible pairwise transform

    C_a = U_a + g*U_b          U_a = th*(C_a + g*C_b)
    C_b = g*U_a + U_b          U_b = th*(g*C_a + C_b)        th = inv(1+g^2)

(char-2 field; a = smaller-x member of the pair; g = GAMMA).  The defining
property: for every plane ``z`` the *uncoupled* symbols across all N nodes
form a codeword of the base MDS code.

One generic *plane-schedule* engine (`_solve`) performs encoding (unknowns =
parity nodes), arbitrary erasure decoding (unknowns = erased nodes, any
``<= m``), exploiting the intersection-score (IS) ordering of planes; a
dedicated `repair` implements the bandwidth-optimal single-node repair that
reads only ``alpha/q`` sub-chunks from each of the ``d = n-1`` helpers —
the MSR property responsible for the paper's "~60% less repair bandwidth
than Reed-Solomon" claim (we measure exact bytes in
``benchmarks/repair_bandwidth.py``).

Storage layout: a chunk is ``(alpha, w)`` bytes; a codeword is ``(n, alpha, w)``.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from collections.abc import Callable

import numpy as np

from repro.core import gf
from repro.core.rs import MDSCode
from repro.spans import span

GAMMA = 2  # gamma^2 != 1  ->  1 + gamma^2 = 5 != 0 in GF(256)
_ONE_PLUS_G2 = 1 ^ gf.pow_(GAMMA, 2)
_THETA = int(gf.inv(np.uint8(_ONE_PLUS_G2)))  # inv(1 + g^2)
_INV_GAMMA = int(gf.inv(np.uint8(GAMMA)))


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _word(w: int) -> np.dtype:
    """The widest unsigned word that packs a row of w bytes exactly."""
    return next(np.dtype(f"u{s}") for s in (8, 4, 2, 1) if w % s == 0)


def _times_gamma(a: np.ndarray) -> np.ndarray:
    """GAMMA * a, in place, on GF(2^8) bytes packed into unsigned words:
    GAMMA = 2 is one xtime per byte lane, ``((a & 0x7f) << 1) ^ ((a >> 7 & 1) * 0x1d)``."""
    lanes = a.dtype.itemsize
    carry = a >> 7
    carry &= int.from_bytes(b"\x01" * lanes, "little")
    carry *= gf.POLY & 0xFF
    a &= int.from_bytes(b"\x7f" * lanes, "little")
    a <<= 1
    a ^= carry
    return a


def _idx(values) -> np.ndarray:
    return np.asarray(values, dtype=np.intp)


@dataclasses.dataclass(frozen=True)
class _GroupPlan:
    """Index arrays for one IS group of `ClayCode._solve`.

    A row is ``flat * alpha + plane`` of the codeword viewed as
    ``(N * alpha, w)``; an out position is a row of the group's matmul
    output, ``(unknown node, plane)`` node-major.
    """

    planes: np.ndarray  # plane indices, ascending
    known_rows: np.ndarray  # known vertices, node-major: the matmul operand's rows
    known_partner: np.ndarray  # each one's partner row (a diagonal vertex: its own)
    known_diag: np.ndarray  # positions in known_rows of the diagonal vertices
    unknown_rows: np.ndarray  # unknown vertices: partner known | partner unknown | diagonal
    unknown_out: np.ndarray  # each one's out position
    partner_known: np.ndarray  # rows of the known partners of the first block
    partner_unknown: np.ndarray  # out positions of the unknown partners of the second


@dataclasses.dataclass(frozen=True)
class _Plan:
    r_theta: np.ndarray  # th * R: the groups' solver, taking V to U_unknown
    known: tuple[int, ...]  # flats of the matmul operand's node rows
    groups: tuple[_GroupPlan, ...]  # ascending IS score


@dataclasses.dataclass(frozen=True)
class ClayCode:
    """(n=k+m, k, d=n-1) Clay code over GF(2^8)."""

    k: int
    m: int
    # the GF matmul of the encode's parity solve (None: numpy); not part of
    # the code's identity, so a bound copy shares its cached plans
    matmul: Callable[[np.ndarray, np.ndarray], np.ndarray] | None = dataclasses.field(
        default=None, compare=False, repr=False)

    def __post_init__(self):
        assert self.k >= 1 and self.m >= 1

    # -- derived parameters ---------------------------------------------------
    @property
    def n(self) -> int:
        return self.k + self.m

    @property
    def d(self) -> int:
        return self.n - 1

    @property
    def q(self) -> int:
        return self.m

    @functools.cached_property
    def t(self) -> int:
        return _ceil_div(self.n, self.q)

    @property
    def N(self) -> int:  # extended (padded) code length
        return self.q * self.t

    @property
    def num_virtual(self) -> int:
        return self.N - self.n

    @functools.cached_property
    def alpha(self) -> int:  # sub-packetization
        return self.q**self.t

    @functools.cached_property
    def base(self) -> MDSCode:
        return MDSCode(n=self.N, k=self.N - self.m)

    # -- node indexing --------------------------------------------------------
    # Extended flat index f = y*q + x.  Real chunks occupy:
    #   data chunks   0..k-1        -> flats 0..k-1
    #   virtual zeros               -> flats k..K'-1   (K' = N - m)
    #   parity chunks k..n-1        -> flats K'..N-1
    @functools.cached_property
    def real_to_flat(self) -> tuple[int, ...]:
        kprime = self.N - self.m
        return tuple(range(self.k)) + tuple(range(kprime, self.N))

    @functools.cached_property
    def virtual_flats(self) -> tuple[int, ...]:
        return tuple(range(self.k, self.N - self.m))

    def _xy(self, flat: int) -> tuple[int, int]:
        return flat % self.q, flat // self.q

    def _flat(self, x: int, y: int) -> int:
        return y * self.q + x

    # -- z-plane utilities ----------------------------------------------------
    @functools.cached_property
    def planes(self) -> list[tuple[int, ...]]:
        return [tuple(z) for z in itertools.product(range(self.q), repeat=self.t)]

    @functools.cached_property
    def plane_index(self) -> dict[tuple[int, ...], int]:
        return {z: i for i, z in enumerate(self.planes)}

    def _partner(self, x: int, y: int, z: tuple[int, ...]):
        """Partner vertex of (x,y,z) or None if diagonal (z_y == x)."""
        if z[y] == x:
            return None
        zp = list(z)
        zp[y] = x
        return z[y], y, tuple(zp)

    def _pair_order(self, x_a: int, x_b: int) -> bool:
        """True if vertex with x_a is the 'a' (smaller-x) member."""
        return x_a < x_b

    @staticmethod
    def _u_from_pair(c_self, c_partner, self_is_a: bool):
        """Uncoupled value of `self` from both coupled values."""
        if self_is_a:
            return gf.mul(_THETA, c_self ^ gf.mul(GAMMA, c_partner))
        return gf.mul(_THETA, gf.mul(GAMMA, c_partner) ^ c_self)

    # -- the generic plane-schedule engine -------------------------------------
    def _is_score(self, z: tuple[int, ...], unknown: frozenset[int]) -> int:
        return sum(1 for y in range(self.t) if self._flat(z[y], y) in unknown)

    @functools.lru_cache(maxsize=64)
    def _decode_mats(self, unknown: tuple[int, ...]) -> tuple[np.ndarray, tuple[int, ...]]:
        """(R, known_used): per-plane solver U_unknown = R @ U_known_used."""
        e = len(unknown)
        known = tuple(i for i in range(self.N) if i not in set(unknown))
        h = self.base.parity_check[:e, :]
        he = h[:, list(unknown)]
        hk = h[:, list(known)]
        r = gf.matmul_np(gf.mat_inv(he), hk)
        return r, known

    @functools.lru_cache(maxsize=64)
    def _plan(self, unknown: tuple[int, ...]) -> _Plan:
        """The index plan `_solve` runs for one erasure pattern (sorted flats).

        Built once per pattern; ``ClayCode._plan.cache_info()`` counts builds
        (misses) against uses (hits).
        """
        unknown_set = frozenset(unknown)
        r_mat, known = self._decode_mats(unknown)
        alpha = self.alpha
        by_score: dict[int, list[int]] = {}
        for z in self.planes:
            by_score.setdefault(self._is_score(z, unknown_set), []).append(self.plane_index[z])

        def row(f: int, zi: int) -> int:
            return f * alpha + zi

        def partner(f: int, zi: int) -> tuple[int, int] | None:
            """(flat, plane) of the vertex paired with (f, zi); None on the diagonal."""
            p = self._partner(*self._xy(f), self.planes[zi])
            return None if p is None else (self._flat(p[0], p[1]), self.plane_index[p[2]])

        groups = []
        for score in sorted(by_score):
            zis = by_score[score]
            # known vertices, node-major: the rows of the matmul's operand
            known_rows, known_partner, known_diag = [], [], []
            for f in known:
                for zi in zis:
                    p = partner(f, zi)
                    if p is None:
                        known_diag.append(len(known_rows))
                    known_rows.append(row(f, zi))
                    known_partner.append(row(f, zi) if p is None else row(*p))
            # unknown vertices: (row, out position, the partner's row or out position)
            out_pos = {v: i for i, v in enumerate(itertools.product(unknown, zis))}
            with_known, with_unknown, diag = [], [], []
            for (f, zi), i in out_pos.items():
                p = partner(f, zi)
                if p is None:
                    diag.append((row(f, zi), i, -1))
                elif p[0] in unknown_set:
                    # the partner's plane has the same IS score: it is in this group
                    with_unknown.append((row(f, zi), i, out_pos[p]))
                else:
                    with_known.append((row(f, zi), i, row(*p)))
            ordered = with_known + with_unknown + diag
            groups.append(_GroupPlan(
                planes=_idx(zis),
                known_rows=_idx(known_rows),
                known_partner=_idx(known_partner),
                known_diag=_idx(known_diag),
                unknown_rows=_idx([r for r, _, _ in ordered]),
                unknown_out=_idx([i for _, i, _ in ordered]),
                partner_known=_idx([s for _, _, s in with_known]),
                partner_unknown=_idx([s for _, _, s in with_unknown]),
            ))
        return _Plan(r_theta=gf.mul(_THETA, r_mat), known=known, groups=tuple(groups))

    def _solve(
        self, c: np.ndarray, unknown_flats: frozenset[int], matmul=None
    ) -> np.ndarray:
        """Fill in coupled values of `unknown_flats` given all other nodes.

        c: (N, alpha, w) uint8 with known nodes' coupled values populated
        (virtual nodes are zero).  Fills in the unknowns in place and returns
        c (a C-contiguous copy, filled in, where c is not C-contiguous).
        Precondition: len(unknown_flats) <= m.

        `matmul` swaps the GF backend for the per-group linear solves
        ((M,K) x (K,N) -> (M,N) over GF(2^8)); defaults to the numpy
        table path, and accepts `repro.kernels.ops.gf_matmul_np` to route
        the wide payload product through the Pallas kernel.

        Each IS group of planes (ascending score) is three whole-array passes
        over the rows of `_plan`, with the pairwise transform rewritten so
        that only GAMMA multiplies (one xtime) are left on the host:

        1. uncouple: ``V = C + g*C_partner``, or ``(1+g^2)*C`` on the
           diagonal, so that ``U = th*V`` for every known vertex.
        2. solve: ``U_unknown = (th*R) @ V``, one matmul for the group.
        3. couple: ``C = U`` on the diagonal, ``U + g*U_partner`` where the
           partner is unknown (its plane is in the same group), and
           ``U + g*(g*U + C_partner) = (1+g^2)*U + g*C_partner`` where it is
           known.
        """
        matmul = matmul or gf.matmul_np
        assert len(unknown_flats) <= self.m, "more erasures than parities"
        if not unknown_flats:
            return c
        unknown = tuple(sorted(unknown_flats))
        plan = self._plan(unknown)
        w = c.shape[-1]
        word = _word(w)
        c = np.ascontiguousarray(c)
        rows = c.reshape(self.N * self.alpha, w).view(word)  # writes land in c
        for g in plan.groups:
            # one span per step of each IS group, never per plane
            with span("shelby.clay.uncouple"):
                v = np.take(rows, g.known_rows, axis=0)
                p = np.take(rows, g.known_partner, axis=0)
                p[g.known_diag] = _times_gamma(p[g.known_diag])
                v ^= _times_gamma(p)
            with span("shelby.clay.solve"):
                rec = matmul(plan.r_theta, v.view(np.uint8).reshape(len(plan.known), -1))
                u = np.ascontiguousarray(rec, np.uint8).view(word).reshape(len(g.unknown_out), -1)
            with span("shelby.clay.couple"):
                out = np.take(u, g.unknown_out, axis=0)
                n_known, n_unknown = len(g.partner_known), len(g.partner_unknown)
                mixed = _times_gamma(out[:n_known].copy())
                mixed ^= np.take(rows, g.partner_known, axis=0)
                out[:n_known] ^= _times_gamma(mixed)
                partners = np.take(u, g.partner_unknown, axis=0)
                out[n_known : n_known + n_unknown] ^= _times_gamma(partners)
                rows[g.unknown_rows] = out
        return c

    # -- public API -------------------------------------------------------------
    def _blank(self, w: int) -> np.ndarray:
        return np.zeros((self.N, self.alpha, w), dtype=np.uint8)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data: (k, alpha, w) -> full codeword (n, alpha, w).

        The parity solve runs through the code's `matmul`: bound to
        ``repro.kernels.ops.gf_matmul_np``, the (m, N - m) x (N - m, alpha * w)
        product runs on the device.  The parity bytes are the same either way."""
        with span("shelby.clay.encode"):
            data = np.asarray(data, dtype=np.uint8)
            assert data.shape[:2] == (self.k, self.alpha), data.shape
            c = self._blank(data.shape[2])
            c[: self.k] = data
            unknown = frozenset(self.real_to_flat[self.k :])
            c = self._solve(c, unknown, matmul=self.matmul)
            return c[list(self.real_to_flat)]

    def decode(self, shards: dict[int, np.ndarray]) -> np.ndarray:
        """Reconstruct all n chunks from any >= k of them (MDS property)."""
        if len(shards) < self.k:
            raise ValueError(f"need >= k={self.k} shards, got {len(shards)}")
        w = next(iter(shards.values())).shape[-1]
        c = self._blank(w)
        present = set(shards)
        for real, flat in enumerate(self.real_to_flat):
            if real in present:
                c[flat] = shards[real]
        erased = [self.real_to_flat[i] for i in range(self.n) if i not in present]
        # keep only m unknowns: with > k shards present this is automatic
        c = self._solve(c, frozenset(erased))
        return c[list(self.real_to_flat)]

    def reconstruct_data(self, shards: dict[int, np.ndarray]) -> np.ndarray:
        return self.decode(shards)[: self.k]

    # -- batched decode (§3.5 erasure-coding acceleration) -------------------------
    def decode_batch(
        self, shard_sets: list[dict[int, np.ndarray]], *, matmul=None
    ) -> list[np.ndarray]:
        """Decode many chunksets' shard sets through few wide GF calls.

        Chunksets sharing an *erasure pattern* are stacked along the byte
        (w) axis and pushed through the plane-schedule engine once, so each
        IS-group linear solve becomes a single (e, K') x (K', G*B*w) GF
        matmul instead of B narrow ones — wide enough to amortize a Pallas
        `gf_matmul` dispatch (pass ``matmul=repro.kernels.ops.gf_matmul_np``).
        Byte-identical to calling `decode` per chunkset.
        """
        if not shard_sets:
            return []
        with span("shelby.clay.decode"):
            out: list[np.ndarray | None] = [None] * len(shard_sets)
            groups: dict[tuple[int, ...], list[int]] = {}
            for i, shards in enumerate(shard_sets):
                if len(shards) < self.k:
                    raise ValueError(f"need >= k={self.k} shards, got {len(shards)}")
                erased = tuple(
                    self.real_to_flat[r] for r in range(self.n) if r not in shards
                )
                groups.setdefault(erased, []).append(i)
            for erased, idxs in groups.items():
                w = next(iter(shard_sets[idxs[0]].values())).shape[-1]
                c = np.zeros((self.N, self.alpha, w * len(idxs)), dtype=np.uint8)
                for b, i in enumerate(idxs):
                    for real, shard in shard_sets[i].items():
                        assert shard.shape == (self.alpha, w), shard.shape
                        c[self.real_to_flat[real], :, b * w : (b + 1) * w] = shard
                c = self._solve(c, frozenset(erased), matmul=matmul)
                full = c[list(self.real_to_flat)]
                for b, i in enumerate(idxs):
                    out[i] = np.ascontiguousarray(full[:, :, b * w : (b + 1) * w])
            return out

    def reconstruct_data_batch(
        self, shard_sets: list[dict[int, np.ndarray]], *, matmul=None
    ) -> list[np.ndarray]:
        return [cw[: self.k] for cw in self.decode_batch(shard_sets, matmul=matmul)]

    # -- bandwidth-optimal single-node repair -------------------------------------
    def repair_planes(self, failed_real: int) -> list[tuple[int, ...]]:
        x0, y0 = self._xy(self.real_to_flat[failed_real])
        return [z for z in self.planes if z[y0] == x0]

    def repair_subchunk_ids(self, failed_real: int) -> list[int]:
        """Sub-chunk indices every helper must transmit (alpha/q of them)."""
        return [self.plane_index[z] for z in self.repair_planes(failed_real)]

    def repair_bandwidth_bytes(self, chunk_bytes: int) -> int:
        """Helper bytes read to repair ONE chunk (MSR optimum, d = n-1)."""
        return (self.n - 1) * (chunk_bytes // self.q)

    def repair(
        self,
        failed_real: int,
        helper_subchunks: dict[int, np.ndarray],
    ) -> np.ndarray:
        """Repair chunk `failed_real` from helpers' repair-plane sub-chunks.

        helper_subchunks: {real_idx: (alpha/q, w)} — ONLY the sub-chunks whose
        plane z satisfies z_{y0} == x0, in `repair_subchunk_ids` order.
        Requires all d = n-1 helpers (optimal-bandwidth regime); for fewer
        helpers fall back to `decode` (MDS path), as §3.3 prescribes.
        """
        f_flat = self.real_to_flat[failed_real]
        x0, y0 = self._xy(f_flat)
        rplanes = self.repair_planes(failed_real)
        if set(helper_subchunks) != set(range(self.n)) - {failed_real}:
            raise ValueError("optimal repair needs all n-1 helpers")
        w = next(iter(helper_subchunks.values())).shape[-1]

        # Coupled values on repair planes, indexed by extended flat id and
        # *local* repair-plane position (virtual nodes: zeros).
        rp_index = {z: i for i, z in enumerate(rplanes)}
        c_rp = np.zeros((self.N, len(rplanes), w), dtype=np.uint8)
        for real, sub in helper_subchunks.items():
            assert sub.shape == (len(rplanes), w), sub.shape
            c_rp[self.real_to_flat[real]] = sub

        # Column-y0 nodes hold the per-plane unknown uncoupled values.
        col_nodes = [self._flat(x, y0) for x in range(self.q)]
        col_set = set(col_nodes)
        known_nodes = [f for f in range(self.N) if f not in col_set]

        # U of non-column nodes: partners stay inside the repair-plane set.
        u_rp = np.zeros_like(c_rp)
        for z in rplanes:
            ri = rp_index[z]
            for f in known_nodes:
                x, y = self._xy(f)
                p = self._partner(x, y, z)
                if p is None:
                    u_rp[f, ri] = c_rp[f, ri]
                else:
                    px, py, pz = p
                    u_rp[f, ri] = self._u_from_pair(
                        c_rp[f, ri],
                        c_rp[self._flat(px, py), rp_index[pz]],
                        self._pair_order(x, px),
                    )

        # Solve the q unknown column-U values per plane with the base code.
        e = len(col_nodes)
        h = self.base.parity_check[:e, :]
        r_mat = gf.matmul_np(gf.mat_inv(h[:, col_nodes]), h[:, known_nodes])
        kn = u_rp[known_nodes].reshape(len(known_nodes), -1)
        sol = gf.matmul_np(r_mat, kn).reshape(e, len(rplanes), w)
        u_col = {f: sol[i] for i, f in enumerate(col_nodes)}

        # Assemble the failed chunk.
        out = np.zeros((self.alpha, w), dtype=np.uint8)
        for z in self.planes:
            zi = self.plane_index[z]
            if z[y0] == x0:
                # repair plane: failed vertex is diagonal -> C = U
                out[zi] = u_col[f_flat][rp_index[z]]
            else:
                # paired with helper vertex p in a repair plane
                x1 = z[y0]
                pz = list(z)
                pz[y0] = x0
                pz = tuple(pz)
                pf = self._flat(x1, y0)
                c_p = c_rp[pf, rp_index[pz]]
                u_p = u_col[pf][rp_index[pz]]
                if self._pair_order(x1, x0):
                    # partner p is 'a', failed vertex is 'b':
                    # U_b = (C_a + U_a)/g ;  C_b = g*U_a + U_b
                    u_b = gf.mul(_INV_GAMMA, c_p ^ u_p)
                    out[zi] = gf.mul(GAMMA, u_p) ^ u_b
                else:
                    # partner p is 'b', failed vertex is 'a':
                    # U_a = (C_b + U_b)/g ;  C_a = U_a + g*U_b
                    u_a = gf.mul(_INV_GAMMA, c_p ^ u_p)
                    out[zi] = u_a ^ gf.mul(GAMMA, u_p)
        return out
