"""Reduce a JAX profiler trace to device busy time, kernel time and idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes (one per device, one for the
host's threads), their lines, and events with a start and a duration in
nanoseconds on one clock.

* Busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices used.
* Program time: the device time of each XLA program by its jitted name, so
  that ``gf_matmul`` is found whatever the compiler names its operations.
* Idle gaps: the stretches of the window in which no device operation ran,
  each named by the benchmark's host span (``bench.*``) that covered most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceSummary:
    window_s: float  # the window span's length on the trace clock
    busy_s: float  # device busy inside the window, mean over devices
    devices: int
    program_s: dict  # jitted program name -> device seconds in the window
    op_s: dict  # device operation name -> device seconds in the window
    gaps: list  # (host span name, seconds) of every idle stretch, longest first

    def program_seconds(self, name: str) -> float:
        """Device seconds of every program whose name contains ``name``."""
        return sum(s for prog, s in self.program_s.items() if name in prog)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _program_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)  # "jit_gf_matmul(42)" -> "jit_gf_matmul"


def _op_name(event_name: str) -> str:
    """"%gf_matmul.1 = u8[6,1050624]{...} custom-call(...)" -> "%gf_matmul.1"."""
    return event_name.split(" = ", 1)[0]


def _union(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(start, end, lo, hi):
    return max(start, lo), min(end, hi)


def reduce(planes) -> TraceSummary:
    """``planes``: the ``planes`` of a ``ProfileData``, or objects shaped alike."""
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            if lines.get(OPS_LINE) or lines.get(MODULES_LINE):
                devices.append(lines)
        else:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    busy_total = 0.0
    program_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    idle = []
    for lines in devices:
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        clipped = []
        for e in ops:
            s, t = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
            if t > s:
                clipped.append((s, t))
                name = _op_name(e.name)
                op_s[name] = op_s.get(name, 0.0) + (t - s) / 1e9
        merged = _union(clipped)
        busy_total += sum(t - s for s, t in merged) / 1e9
        for e in lines.get(MODULES_LINE, []):
            s, t = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
            if t > s:
                name = _program_name(e.name)
                program_s[name] = program_s.get(name, 0.0) + (t - s) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    gaps = []
    for s, t in idle:
        best, cover = "bench.harness", 0
        for name, a, b in inner:
            overlap = min(b, t) - max(a, s)
            if overlap > cover:
                best, cover = name, overlap
        gaps.append((best, (t - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    n = max(len(devices), 1)
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy_total / n, devices=len(devices),
                        program_s=program_s, op_s=op_s, gaps=gaps)


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes)
