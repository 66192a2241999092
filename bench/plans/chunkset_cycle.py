"""Whole chunksets of the stored working set, in blob order, over and over.

Reads every one of the ``stored_blobs`` x ``blob_chunksets`` chunksets once
per cycle; with a working set larger than the fleet's hot cache, an LRU
cache never hits.  The order is the same for every seed.
"""
import itertools

from bench.traffic import Request


def requests(mix, chunkset_bytes, seed):
    per_blob = mix["blob_chunksets"]
    total = mix["stored_blobs"] * per_blob
    return (Request(blob=(i % total) // per_blob, offset=(i % per_blob) * chunkset_bytes,
                    length=chunkset_bytes)
            for i in itertools.count())
