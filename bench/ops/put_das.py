"""Puts of fresh blobs, each extended into its DAS square, and the check of the stored squares.

``bench/ops/put.py`` makes the puts, their set-up and their check.  Where the
configuration guarantees ``das_square_exact`` and ``das_on_device``, this op
adds exact counts, each with the limit 0, of the squares the SPs hold against
the plain reference ``bench/das_reference.py``:

* ``wrong_das_data``: shares of the data quadrant unequal to the blob's
  bytes, zero-padded to the k x k square;
* ``wrong_das_lines``: rows and columns that fail a syndrome test by the
  reference's parity check H: 4 combinations of H's rows with seeded random
  coefficients per axis.  A line that is not a codeword has a nonzero
  syndrome at some byte position, and each combination misses it with
  probability 1/256, so a bad line escapes all four with probability 2^-32;
* ``wrong_das_roots``: puts whose on-chain DAS root is not the reference's
  root over the stored shares;
* ``wrong_das_extension``: shares of the first acknowledged put's stored
  square unequal to the reference's extension of its blob, computed a block
  of share bytes at a time;
* ``host_das_extensions``: puts whose two axis extensions did not both reach
  the node's device matmul, seen by the shapes the deployment's kernel log
  records during the put.

A share missing from its SP counts as wrong wherever it is compared.  The
checks run before the put check's read-back, with every SP up.

The program has to have a kernel for the square's (k, k) coefficient matrix:
one without ``kernels.ops.uses_bit_matrix`` would trace its GF kernel
unrolled over every coefficient, so set-up refuses to start there.
"""
from pathlib import Path

import numpy as np

from bench import das_reference as ref
from bench import parts, traffic

Put = parts.load(Path(__file__).resolve().parents[2], "ops", "put").Op

SYNDROME_COMBINATIONS = 4
BLOCK_BYTES = 64  # share bytes extended at a time by the reference


class Op(Put):
    def __init__(self, dep, mix, seed, log):
        from repro.kernels import ops

        if not hasattr(ops, "uses_bit_matrix"):
            raise RuntimeError("this program has no GF kernel for wide coefficient matrices: "
                               "a DAS square's extension would trace the VPU kernel unrolled "
                               f"over {dep.config['das_k'] ** 2} coefficients")
        k, s = dep.config["das_k"], dep.config["das_share_bytes"]
        self.k, self.share_bytes = k, s
        self.extension_shapes = {(k, k, k * s), (k, k, 2 * k * s)}
        self.on_device: dict[int, bool] = {}  # blob id -> both extensions on the device
        super().__init__(dep, mix, seed, log)  # the set-up put compiles both extensions
        n = 2 * k
        combos = traffic.rng_for(seed, 5).integers(0, 256, (2, SYNDROME_COMBINATIONS, k),
                                                   dtype=np.uint8)
        self.syndrome = [ref.matmul(c, ref.parity_check(n, k)) for c in combos]

    def issue(self, req):
        shapes = self.dep.kernels.shapes
        before = len(shapes)
        meta = super().issue(req)
        self.on_device[meta.blob_id] = self.extension_shapes <= set(shapes[before:])
        return meta

    def check(self, checks, done) -> None:
        guarantees = self.dep.config["guarantees"]
        if guarantees.get("das_square_exact"):
            self._check_squares(checks, done)
        if guarantees.get("das_on_device"):
            checks.add("host_das_extensions",
                       sum(not self.on_device.get(d.answer.blob_id, False)
                           for d in done if d.error is None), 0)
        super().check(checks, done)

    def _check_squares(self, checks, done) -> None:
        k = self.k
        data = lines = roots = extension = 0
        for i, d in enumerate([d for d in done if d.error is None]):
            square = self._data_square(traffic.put_blob(self.mix, self.seed, d.request.blob))
            stored, missing, root = self._stored(d.answer.blob_id)
            data += int(np.sum(np.any(stored[:k, :k] != square, axis=2) | missing[:k, :k]))
            lines += self._bad_lines(stored, missing)
            roots += root is None or missing.any() or root != ref.square_roots(stored)[2]
            if i == 0:
                extension = self._wrong_extension(square, stored, missing)
        self.log(f"checked the stored DAS squares of the acknowledged puts against the "
                 f"reference: {2 * k} x {2 * k} shares of {self.share_bytes} bytes each")
        checks.add("wrong_das_data", data, 0)
        checks.add("wrong_das_lines", lines, 0)
        checks.add("wrong_das_roots", int(roots), 0)
        checks.add("wrong_das_extension", extension, 0)

    def _data_square(self, blob: bytes) -> np.ndarray:
        k, s = self.k, self.share_bytes
        flat = np.zeros(k * k * s, np.uint8)
        head = np.frombuffer(blob[: flat.size], np.uint8)
        flat[: head.size] = head
        return flat.reshape(k, k, s)

    def _stored(self, blob_id: int):
        """The square the SPs hold, which shares are missing, and the on-chain root."""
        side = 2 * self.k
        stored = np.zeros((side, side, self.share_bytes), np.uint8)
        missing = np.ones((side, side), bool)
        record = self.dep.contract.das.get(blob_id)
        if record is None:
            return stored, missing, None
        for (r, c), sp_id in record.placement.items():
            got = self.dep.sps[sp_id].serve_share(blob_id, r, c)
            if got is not None:
                stored[r, c] = got[0]
                missing[r, c] = False
        return stored, missing, record.das_root

    def _bad_lines(self, stored, missing) -> int:
        """Rows, then columns, with a nonzero syndrome or a missing share."""
        side = 2 * self.k
        bad = 0
        for axis, g in enumerate(self.syndrome):
            # symbol axis first: a column's symbols are rows, a row's are columns
            by_symbol = stored if axis == 0 else stored.transpose(1, 0, 2)
            syn = ref.matmul(g, by_symbol.reshape(side, -1)).reshape(len(g), side, -1)
            bad += int(np.sum(syn.any(axis=(0, 2)) | missing.any(axis=axis)))
        return bad

    def _wrong_extension(self, square, stored, missing) -> int:
        wrong = missing.copy()
        for lo in range(0, self.share_bytes, BLOCK_BYTES):
            hi = lo + BLOCK_BYTES
            wrong |= np.any(ref.extend(square[:, :, lo:hi]) != stored[:, :, lo:hi], axis=2)
        return int(wrong.sum())

    def work(self, done) -> dict:
        """The put's work, and the client's DAS counters over the whole run
        (the set-up put among them)."""
        stats = self.dep.client.stats
        return dict(super().work(done), das_side=2 * self.k,
                    das_squares_extended=stats.das_squares_extended,
                    das_shares_placed=stats.das_shares_placed)
