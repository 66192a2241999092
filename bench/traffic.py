"""The one traffic generator: a mix file of parameters -> the work of a run.

A mix (``bench/mixes/<name>.json``) is data only.  It names the parts that
carry it out, each a file found by name (``bench/parts.py``), and gives
their parameters:

* ``op``: ``bench/ops/<op>.py``, what one request does (a read of a stored
  range, a put of a fresh blob), the set-up it needs and its check.
* ``plan``: ``bench/plans/<plan>.py``, the requests in the order they are
  sent.
* ``loop``: ``bench/loops/<loop>.py``, how the clients send them.
* ``stored_blobs`` x ``blob_chunksets`` (reads): the working set written
  through ``ShelbyClient.put`` in set-up.
* ``erased_data_chunks`` (reads): before the window, the fewest SPs are
  crashed that leave every stored chunkset with at least this many data
  chunks erased (0 crashes none).
* ``put_bytes`` (puts): the size of each fresh blob.
* ``control``: the fault of ``bench/faults.py`` that serves as the cell's
  control (``bench/tools.py control --fault control``).

A plan takes from the seed only the order of its requests, and the bytes
come from the seed: every seed gets the same sizes and offsets, so runs with
different seeds do the same work.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from bench import parts


@dataclasses.dataclass(frozen=True)
class Request:
    blob: int  # index into the stored blobs (reads) or the put counter (puts)
    offset: int = 0
    length: int = 0


@dataclasses.dataclass
class Done:
    request: Request
    start_s: float  # when it was sent, seconds after the window opened
    end_s: float  # when it returned
    answer: object = None  # bytes read, or the metadata of an acknowledged put
    error: str | None = None


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one stream of draws of a run; any whole seed will do."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def stored_blobs(mix: dict, chunkset_bytes: int, seed: int) -> list[bytes]:
    """The bytes of every blob stored in set-up, from the seed."""
    rng = rng_for(seed, 1)
    return [rng.bytes(mix["blob_chunksets"] * chunkset_bytes) for _ in range(mix["stored_blobs"])]


def put_blob(mix: dict, seed: int, index: int) -> bytes:
    """The ``index``-th fresh blob of a put stream, from the seed."""
    return rng_for(seed, 2, index).bytes(mix["put_bytes"])


def plan(root: Path, mix: dict, chunkset_bytes: int, seed: int):
    """The requests of the mix's plan, in the order they are sent."""
    return parts.load(root, "plans", mix["plan"]).requests(mix, chunkset_bytes, seed)


def run_loop(root: Path, mix: dict, requests, issue, name: str, seconds: float, clock, span):
    """Send ``requests`` through ``issue`` by the mix's loop, each under a
    host span ``name``, for ``seconds``; return the window's start on
    ``clock`` and the completed requests."""
    return parts.load(root, "loops", mix["loop"]).run(requests, issue, name, seconds, clock, span)


def call(issue, req: Request):
    """The answer of one request, or the error it raised."""
    try:
        return issue(req), None
    except Exception as e:  # a failed request is counted, and the run goes on
        return None, f"{type(e).__name__}: {e}"
