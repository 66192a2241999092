"""Puts of the ``stored_blobs`` x ``blob_chunksets`` blobs of a cycle, in turn, over and over.

Request i puts blob i modulo the cycle's length, which ``traffic.put_blob``
makes from the seed; each put is a new blob of the program, however often
its bytes have been put before.  A cycle longer than a window's puts makes
every put of a window fresh bytes as well.  The order is the same for
every seed.
"""
import itertools

from bench.traffic import Request


def requests(mix, chunkset_bytes, seed):
    cycle = mix["stored_blobs"] * mix["blob_chunksets"]
    return (Request(blob=i % cycle, length=mix["put_bytes"]) for i in itertools.count())
