"""Faults planted under the timed path, and the controls built from them.

Each fault patches one class of the program for the life of a process, so
that a run driven through the harness shows whether the comparison in
``check.py`` catches it.  The benchmark's own runs never plant one: only
``bench/tools.py control`` and the tests do.

A control breaks a guarantee the configuration states, as the shortcut a
later change might be tempted by: ``skip_decode`` serves the data chunks
it fetched and zeros for the rest instead of Clay-decoding; ``skip_parity``
stores zero parity instead of Clay-encoding.  The other faults alter an
answer where it is produced, drop half of one, keep the state unchanged,
get one put's parity wrong, move the decode to the host, break the
settlement, or refuse the request.
"""
from __future__ import annotations

import contextlib

import numpy as np

def _skip_decode(patch):
    from repro.core.clay import ClayCode

    def reconstruct(self, shard_sets, *, matmul=None):
        out = []
        for shards in shard_sets:
            w = next(iter(shards.values())).shape[-1]
            data = np.zeros((self.k, self.alpha, w), np.uint8)
            for i in range(self.k):
                if i in shards:
                    data[i] = shards[i]
            out.append(data)
        return out

    patch(ClayCode, "reconstruct_data_batch", reconstruct)


def _flip_byte(patch):
    from repro.core.clay import ClayCode

    real = ClayCode.reconstruct_data_batch

    def reconstruct(self, shard_sets, *, matmul=None):
        out = real(self, shard_sets, matmul=matmul)
        for data in out:
            data.reshape(-1)[12345 % data.size] ^= 0x01
        return out

    patch(ClayCode, "reconstruct_data_batch", reconstruct)


def _host_decode(patch):
    from repro.kernels import ops

    patch(ops, "resolve_decode_matmul", lambda choice="auto", device=None: None)


def _half_read(patch):
    from repro.storage.blob import BlobLayout

    real = BlobLayout.extract_range

    def extract(self, chunksets, first, offset, length, blob_len):
        data = real(self, chunksets, first, offset, length, blob_len)
        return data[: len(data) // 2]

    patch(BlobLayout, "extract_range", extract)


def _stale_read(patch):
    """Each read returns the answer of the read before it."""
    from repro.storage.blob import BlobLayout

    real = BlobLayout.extract_range
    last = [None]

    def extract(self, chunksets, first, offset, length, blob_len):
        data = real(self, chunksets, first, offset, length, blob_len)
        prev, last[0] = last[0], data
        return data if prev is None else prev

    patch(BlobLayout, "extract_range", extract)


def _skip_parity(patch):
    from repro.core.clay import ClayCode

    def encode(self, data):
        data = np.asarray(data, np.uint8)
        out = np.zeros((self.n,) + data.shape[1:], np.uint8)
        out[: self.k] = data
        return out

    patch(ClayCode, "encode", encode)


def _flip_parity_once(patch):
    """One byte of one parity chunk of the first chunkset encoded is wrong,
    as from a batched encode that misses one put; its commitments match."""
    from repro.core.clay import ClayCode

    real = ClayCode.encode
    calls = [0]

    def encode(self, data):
        out = real(self, data)
        calls[0] += 1
        if calls[0] == 1:
            out = np.array(out, np.uint8)
            out[self.k].reshape(-1)[777 % out[self.k].size] ^= 0x01
        return out

    patch(ClayCode, "encode", encode)


def _lose_put(patch):
    """Acknowledge a put without storing it: the state stays unchanged."""
    from repro.storage.rpc import RPCNode

    def write_blob(self, meta, encoded_chunksets):
        self.contract.mark_ready(meta.blob_id, self.rpc_id)

    patch(RPCNode, "write_blob", write_blob)


def _flip_stored(patch):
    """Alter one byte of every chunk an RPC node disperses."""
    from repro.storage.sp import StorageProvider

    real = StorageProvider.store_chunk

    def store(self, blob_id, chunkset, chunk, data):
        data = np.array(data, np.uint8)
        data.reshape(-1)[0] ^= 0x01
        return real(self, blob_id, chunkset, chunk, data)

    patch(StorageProvider, "store_chunk", store)


def _double_charge(patch):
    """Every micropayment debits its channel twice what the receipt says."""
    from repro.core.payments import MicropaymentChannel

    real = MicropaymentChannel.pay

    def pay(self, amount):
        return real(self, 2 * amount)

    patch(MicropaymentChannel, "pay", pay)


def _refuse_reads(patch):
    from repro.net.fleet import RPCFleet
    from repro.storage.rpc import ReadError

    def serve_ranges(self, ranges, *, client=None, t_ms=0.0):
        raise ReadError("refused")

    patch(RPCFleet, "serve_ranges", serve_ranges)


def _refuse_puts(patch):
    from repro.storage.rpc import RPCNode

    def write_blob(self, meta, encoded_chunksets):
        raise IOError("refused")

    patch(RPCNode, "write_blob", write_blob)


FAULTS = {
    "skip_decode": _skip_decode,
    "flip_byte": _flip_byte,
    "host_decode": _host_decode,
    "half_read": _half_read,
    "stale_read": _stale_read,
    "skip_parity": _skip_parity,
    "flip_parity_once": _flip_parity_once,
    "lose_put": _lose_put,
    "flip_stored": _flip_stored,
    "double_charge": _double_charge,
    "refuse_reads": _refuse_reads,
    "refuse_puts": _refuse_puts,
}


# faults that set-up would trip over: planted only while the window runs
WINDOW_ONLY = {"double_charge", "refuse_reads", "refuse_puts", "flip_parity_once"}


@contextlib.contextmanager
def _patched(plant):
    saved = []

    def patch(owner, attr, value):
        saved.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, value)

    plant(patch)
    try:
        yield
    finally:
        for owner, attr, value in reversed(saved):
            setattr(owner, attr, value)


def _in_window(fault):
    """Plant ``fault`` for the length of the measured loop of the harness."""
    from bench import traffic

    def plant(patch):
        real = traffic.run_loop

        def during(*args, **kwargs):
            with _patched(fault):
                return real(*args, **kwargs)

        patch(traffic, "run_loop", during)

    return plant


def planted(name: str | None):
    """Plant the named fault (None plants nothing) until the block exits."""
    if name is None:
        return contextlib.nullcontext()
    fault = FAULTS[name]
    return _patched(_in_window(fault) if name in WINDOW_ONLY else fault)
