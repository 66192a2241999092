"""What one run measured, and the per-layer metric readers that reduce it.

Each per-layer metric of ``BENCHMARK.json`` has a reader of its own,
``bench/metrics/<name>.py``, with one function ``read(r: Reading)`` that
returns the metric's value, or None where the run gave it nothing to read
(the harness then leaves the metric out).  The harness finds a reader by the
metric's name alone (``bench/parts.py``), so a new metric is a new file.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass
class Reading:
    done: list  # traffic.Done of every request in the window
    counters: dict  # program counters, their change over the window
    kernel_shapes: list  # (M, K, N) of every gf_matmul call in the window
    trace: object = None  # trace.TraceSummary of a traced run, else None
    chunksets: int = 0  # chunksets the window's requests decoded or encoded
    root: Path | None = None  # the checkout, where bench/peaks.json is
    device_kind: str = ""

    @property
    def peak(self) -> dict:
        """The device's row of ``bench/peaks.json`` (an unknown kind raises)."""
        return peaks(self.root, self.device_kind)


def peaks(root: Path, device_kind: str) -> dict:
    """The published peaks of one chip; a kind missing from the table is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json") from None
