"""The comparison that decides ``correct``.

The reference is plain: the bytes the harness made from the seed and
handed to ``ShelbyClient.put``.  Nothing here imports the program.  Each
number compared has a limit, and a run is correct when every number is at
or under its limit.  The guarantees a configuration states
(``guarantees`` in its file) say which numbers are compared.
"""
from __future__ import annotations

import numpy as np


class Checks:
    """Named numbers, each beside its limit."""

    def __init__(self):
        self.items: dict[str, dict] = {}

    def add(self, name: str, value, limit) -> None:
        self.items[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(c["value"] <= c["limit"] for c in self.items.values())


def wrong_reads(done, sources: list[bytes]) -> int:
    """Reads whose bytes are not the source's bytes at that range."""
    wrong = 0
    for d in done:
        if d.error is not None:
            continue
        r = d.request
        src = sources[r.blob]
        if d.answer != src[r.offset:min(r.offset + r.length, len(src))]:
            wrong += 1
    return wrong


def data_chunks(source: bytes, k: int, alpha: int, w: int) -> list[np.ndarray]:
    """The k systematic chunks of every chunkset of a blob, chunkset by
    chunkset: the blob cut into k*alpha*w-byte chunksets, zero-padded."""
    cs_bytes = k * alpha * w
    buf = np.frombuffer(source, np.uint8)
    pad = -buf.size % cs_bytes
    if pad:
        buf = np.concatenate([buf, np.zeros(pad, np.uint8)])
    return list(buf.reshape(-1, k, alpha, w))


def wrong_stored_chunks(puts, sps: dict, config: dict) -> int:
    """Over every acknowledged put: chunks missing from their SP, and data
    chunks whose bytes are not the blob's (a Clay code is systematic, so
    data chunk i of a chunkset is its i-th k-th part)."""
    k, alpha, w = config["k"], config["alpha"], config["w"]
    wrong = 0
    for meta, source in puts:
        for cs, plain in enumerate(data_chunks(source, k, alpha, w)):
            for ck in range(config["n"]):
                served = sps[meta.placement[(cs, ck)]].serve_chunk(meta.blob_id, cs, ck)
                if served is None:
                    wrong += 1
                elif ck < k and not np.array_equal(np.asarray(served[0]).reshape(alpha, w),
                                                    plain[ck]):
                    wrong += 1
    return wrong

