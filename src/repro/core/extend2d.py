"""2-D Reed-Solomon extension for data-availability sampling (DAS).

The DA_ERASURE blueprint (SNIPPETS.md): a k x k *data square* of
fixed-size shares is extended along both axes with the systematic
``[2k, k]`` RS code of ``core/rs.py`` (extension factor 2 per axis),
producing a 2k x 2k *extended square* in which

* every **row** is a codeword of the row code,
* every **column** is a codeword of the column code, and
* any k complete rows (or any k complete columns) determine the whole
  square — so a data-withholding adversary must hide more than a
  (1 - 1/4)-ish fraction of shares before reconstruction fails, and
  hiding ANY share is detectable by uniform sampling.

Commitments bind the square for light clients: one Merkle tree per row
over its 2k share byte-strings, one per column, and a *DAS root* over
the 2*side concatenated row+column roots.  A :class:`ShareProof` carries
the share's path inside its row (or column) tree plus that root's path
inside the DAS tree, so a sampler holding only ``das_root`` verifies a
single share in O(log side) hashes — the proof-carrying tiny read.

The GF data path is the same pluggable matmul the Clay decode uses:
pure numpy (`gf.matmul_np`) or a Pallas kernel via
``repro.kernels.ops.gf_matmul_np``, which gives a (k, k) parity matrix of
256 or more coefficients to the bit-matrix kernel.  :meth:`Extend2D.extend_batch`
deliberately concatenates MANY squares along the byte axis so thousands
of per-share GF ops become ONE small-and-wide (k x k) @ (k x B*k*S)
kernel call — the opposite kernel regime from the few-and-large
chunkset decodes.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools

import numpy as np

from repro.core import commitments as cm
from repro.core import gf
from repro.core.rs import MDSCode


def detection_probability(q: float, s: int) -> float:
    """P[>= 1 of s uniform with-replacement samples hits a withheld share]
    when a fraction ``q`` of the extended square is withheld."""
    return 1.0 - (1.0 - q) ** s


@dataclasses.dataclass(frozen=True)
class ShareProof:
    """Proof that share (row, col) belongs to a square with a given DAS root.

    ``axis`` names the tree the leaf path runs through ("row" or "col");
    ``leaf_path`` authenticates the share inside that axis tree (whose
    root is ``axis_root``), and ``root_path`` authenticates ``axis_root``
    inside the DAS tree (row roots first, then column roots).  The
    coordinates are *bound*: verification checks the leaf index equals
    the in-axis coordinate and the root index equals the axis position,
    so a valid proof for share (r, c) cannot be replayed at (r', c').
    """

    row: int
    col: int
    axis: str  # "row" | "col"
    axis_root: bytes
    leaf_path: cm.MerkleProof  # share -> axis_root
    root_path: cm.MerkleProof  # axis_root -> das_root

    @property
    def nbytes(self) -> int:
        """Modeled wire size: coordinates + both paths + the axis root."""
        return 8 + self.leaf_path.nbytes + len(self.axis_root) + self.root_path.nbytes


@dataclasses.dataclass(frozen=True, eq=False)
class LinePaths:
    """The half of every share proof of one square that depends only on
    the line the share is proved along, as tables indexed by line (rows
    0..side-1, then columns side..2*side-1): each line's root and that
    root's path to the DAS root."""

    side: int
    axis_roots: tuple[bytes, ...]
    root_paths: tuple[cm.MerkleProof, ...]

    def proof(self, row: int, col: int, leaf_path: np.ndarray) -> ShareProof:
        """Share (row, col)'s proof, given its (depth, 32) leaf path from
        :meth:`CommittedSquare.prove_all`."""
        if (row + col) % 2 == 0:
            axis, index, line = "row", col, row
        else:
            axis, index, line = "col", row, self.side + col
        return ShareProof(
            row=row, col=col, axis=axis, axis_root=self.axis_roots[line],
            leaf_path=cm.MerkleProof(index=index, path=_hashes(leaf_path)),
            root_path=self.root_paths[line],
        )


def _hashes(nodes: np.ndarray) -> tuple[bytes, ...]:
    """(n, 32) uint8 -> n 32-byte hashes."""
    flat = nodes.tobytes()
    return tuple([flat[i : i + 32] for i in range(0, len(flat), 32)])


@dataclasses.dataclass(frozen=True)
class SquareCommitment:
    side: int
    share_bytes: int
    row_roots: tuple[bytes, ...]
    col_roots: tuple[bytes, ...]
    das_root: bytes


class CommittedSquare:
    """An extended square plus the Merkle machinery to prove its shares."""

    def __init__(self, ext: np.ndarray):
        side = ext.shape[0]
        assert ext.shape[1] == side and ext.ndim == 3
        self.ext = ext
        self.row_trees = [
            cm.MerkleTree([ext[r, c].tobytes() for c in range(side)])
            for r in range(side)
        ]
        self.col_trees = [
            cm.MerkleTree([ext[r, c].tobytes() for r in range(side)])
            for c in range(side)
        ]
        row_roots = tuple(t.root for t in self.row_trees)
        col_roots = tuple(t.root for t in self.col_trees)
        self.das_tree = cm.MerkleTree(list(row_roots) + list(col_roots))
        self.commitment = SquareCommitment(
            side=side,
            share_bytes=int(ext.shape[2]),
            row_roots=row_roots,
            col_roots=col_roots,
            das_root=self.das_tree.root,
        )

    def share(self, row: int, col: int) -> np.ndarray:
        return self.ext[row, col]

    def prove_all(self) -> tuple[np.ndarray, LinePaths]:
        """Every share's proof as arrays: one gather over the trees' nodes.

        Share (r, c) is proved along its row when ``(r + c) % 2 == 0``,
        else along its column.  Returns the leaf paths, (side, side,
        depth, 32): the sibling of share (r, c) at each level of its axis
        tree, bytes for bytes the ``path`` of :meth:`prove`; and the
        :class:`LinePaths` of the square, whose :meth:`LinePaths.proof`
        turns one share's leaf path into the :class:`ShareProof` that
        :meth:`prove` builds.
        """
        side = self.commitment.side
        depth = len(self.row_trees[0].levels) - 1
        # every node below the roots: row trees level by level, then columns
        nodes = np.frombuffer(b"".join(itertools.chain.from_iterable(
            t.levels[j] for trees in (self.row_trees, self.col_trees)
            for j in range(depth) for t in trees
        )), np.uint8).reshape(-1, 32)
        width = np.array([len(level) for level in self.row_trees[0].levels[:depth]])
        start = np.concatenate([[0], np.cumsum(side * width)])
        # for each share (r, c) and level j: its tree, its leaf index there
        r = np.arange(side)[:, None, None]
        c = np.arange(side)[None, :, None]
        j = np.arange(depth)
        on_row = (r + c) % 2 == 0
        tree = np.where(on_row, r, c)
        at = np.where(on_row, c, r)
        leaf_paths = nodes[np.where(on_row, 0, start[-1]) + start[j] + tree * width
                           + ((at >> j) ^ 1)]
        return leaf_paths, LinePaths(
            side=side,
            axis_roots=self.commitment.row_roots + self.commitment.col_roots,
            root_paths=tuple(self.das_tree.prove(line) for line in range(2 * side)),
        )

    def prove(self, row: int, col: int, axis: str = "row") -> ShareProof:
        side = self.commitment.side
        if axis == "row":
            leaf_path = self.row_trees[row].prove(col)
            axis_root = self.commitment.row_roots[row]
            root_path = self.das_tree.prove(row)
        elif axis == "col":
            leaf_path = self.col_trees[col].prove(row)
            axis_root = self.commitment.col_roots[col]
            root_path = self.das_tree.prove(side + col)
        else:
            raise ValueError(f"axis must be row|col, got {axis!r}")
        return ShareProof(row=row, col=col, axis=axis, axis_root=axis_root,
                          leaf_path=leaf_path, root_path=root_path)


def verify_share(das_root: bytes, side: int, share: bytes,
                 proof: ShareProof) -> bool:
    """Light-client share verification against the DAS root alone.

    Checks the coordinate binding (leaf/root indices match the claimed
    (row, col) and axis), the share's membership in its axis tree, and
    the axis root's membership in the DAS tree.
    """
    if proof.axis == "row":
        if proof.leaf_path.index != proof.col or proof.root_path.index != proof.row:
            return False
    elif proof.axis == "col":
        if (proof.leaf_path.index != proof.row
                or proof.root_path.index != side + proof.col):
            return False
    else:
        return False
    if not cm.verify(proof.axis_root, share, proof.leaf_path):
        return False
    return cm.verify(das_root, proof.axis_root, proof.root_path)


@dataclasses.dataclass(frozen=True)
class Extend2D:
    """The 2-D extension layout: k x k data -> 2k x 2k shares."""

    k: int

    @property
    def side(self) -> int:
        return 2 * self.k

    @functools.cached_property
    def code(self) -> MDSCode:
        return MDSCode(n=self.side, k=self.k)

    # -- encode ---------------------------------------------------------------
    def pad_square(self, data: bytes, share_bytes: int) -> np.ndarray:
        """Zero-pad ``data`` into the (k, k, share_bytes) data square."""
        need = self.k * self.k * share_bytes
        flat = np.frombuffer(data[:need], dtype=np.uint8)
        if flat.size < need:
            flat = np.concatenate([flat, np.zeros(need - flat.size, np.uint8)])
        return flat.reshape(self.k, self.k, share_bytes)

    def extend(self, square: np.ndarray, matmul=None) -> np.ndarray:
        """(k, k, S) data square -> (2k, 2k, S) extended square."""
        return self.extend_batch([square], matmul=matmul)[0]

    def extend_batch(self, squares: list[np.ndarray], matmul=None) -> list[np.ndarray]:
        """Extend MANY squares with TWO wide GF matmuls total.

        Each axis extension is mathematically ``parity = P @ flat`` with
        the same (m, k) systematic parity matrix; concatenating every
        square's flat bytes along the wide axis turns B tiny encodes into
        one (k, k) @ (k, B*k*S) call — the small-and-wide kernel shape.
        """
        matmul = matmul or gf.matmul_np
        if not squares:
            return []
        k, side = self.k, self.side
        shapes = {sq.shape for sq in squares}
        assert all(s[0] == k and s[1] == k for s in shapes), shapes
        widths = [sq.shape[2] for sq in squares]
        # columns first: parity rows k..2k-1 from the k data rows
        flat = np.concatenate(
            [np.ascontiguousarray(sq, np.uint8).reshape(k, -1) for sq in squares],
            axis=1,
        )
        parity = np.asarray(matmul(self.code.encode_matrix, flat), np.uint8)
        col_ext: list[np.ndarray] = []
        off = 0
        for sq, w in zip(squares, widths):
            span = k * w
            top = np.asarray(sq, np.uint8)
            bot = parity[:, off : off + span].reshape(k, k, w)
            col_ext.append(np.concatenate([top, bot], axis=0))  # (2k, k, S)
            off += span
        # then rows: every one of the 2k rows extends from k to 2k shares;
        # transpose so the row axis is the symbol axis of one wide encode
        flat = np.concatenate(
            [e.transpose(1, 0, 2).reshape(k, -1) for e in col_ext], axis=1
        )
        parity = np.asarray(matmul(self.code.encode_matrix, flat), np.uint8)
        out: list[np.ndarray] = []
        off = 0
        for e, w in zip(col_ext, widths):
            span = side * w
            right = parity[:, off : off + span].reshape(k, side, w)
            full = np.concatenate([e.transpose(1, 0, 2), right], axis=0)
            out.append(np.ascontiguousarray(full.transpose(1, 0, 2)))  # (2k, 2k, S)
            off += span
        return out

    # -- reconstruct ----------------------------------------------------------
    def reconstruct_from_rows(self, rows: dict[int, np.ndarray],
                              matmul=None) -> np.ndarray:
        """Any k complete rows (each (2k, S)) -> the full (2k, 2k, S) square.

        Every column is a codeword of the column code with the same known
        symbol pattern, so ONE decode matrix applied to the stacked known
        rows recovers every missing row in one wide GF call.
        """
        return self._reconstruct_axis(rows, axis=0, matmul=matmul)

    def reconstruct_from_cols(self, cols: dict[int, np.ndarray],
                              matmul=None) -> np.ndarray:
        """Any k complete columns (each (2k, S)) -> the full square."""
        return self._reconstruct_axis(cols, axis=1, matmul=matmul)

    def _reconstruct_axis(self, lines: dict[int, np.ndarray], axis: int,
                          matmul=None) -> np.ndarray:
        matmul = matmul or gf.matmul_np
        side = self.side
        known = tuple(sorted(lines))[: self.k]
        if len(known) < self.k:
            raise ValueError(f"need >= k={self.k} lines, got {len(lines)}")
        share_bytes = lines[known[0]].shape[-1]
        r, erased = self.code.decode_matrix(known)
        stacked = np.stack(
            [np.asarray(lines[i], np.uint8).reshape(-1) for i in known], axis=0
        )  # (k, 2k*S)
        out = np.zeros((side, side, share_bytes), np.uint8)
        for i, line in zip(known, stacked):
            out[i] = line.reshape(side, share_bytes)
        if erased:
            rec = np.asarray(matmul(r, stacked), np.uint8)
            for j, i in enumerate(erased):
                out[i] = rec[j].reshape(side, share_bytes)
        if axis == 1:
            out = out.transpose(1, 0, 2)
        return np.ascontiguousarray(out)


def commit_square(ext: np.ndarray) -> CommittedSquare:
    """Row/column/DAS commitments over an extended square."""
    return CommittedSquare(ext)
