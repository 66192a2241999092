"""Puts of fresh blobs of ``put_bytes`` each: request i puts the i-th blob
that ``traffic.put_blob`` makes from the seed."""
import itertools

from bench.traffic import Request


def requests(mix, chunkset_bytes, seed):
    return (Request(blob=i, length=mix["put_bytes"]) for i in itertools.count())
