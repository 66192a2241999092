#!/usr/bin/env python3
"""Split the window's device-idle time by the program's own spans.

    python3 bench/span_split.py --workload <cell> --seeds 1 2 3 --seconds 51

The program marks its layers with ``shelby.*`` spans (``repro.spans``), on
the profiler's clock beside the device's operations.  :func:`split` gives
every nanosecond of the window in which no device operation ran to one span:

* the innermost open ``shelby.*`` span: the latest start, ties going to the
  shorter span;
* where none is open, the innermost open ``bench.*`` span of the harness
  (the window's own span aside), else ``bench.harness``.

So the idle seconds of all names sum to the window less the device's busy
time: the host gap that ``bench/trace.py`` gives as one number.  Each idle
stretch is named by the span that got most of it, ``bench.harness`` only
where no span got any (with harness spans alone, the names ``bench/trace.py``
gives).  ``OPS`` sums the names into
layers by the op a cell's mix names (``read`` or ``put``), so a new mix of
either op is split with no edit here; within a run the groups split the host
gap.

The command runs a cell once per seed in one process, traced as the
harness's ``--trace 1`` run is (the profiler on over the window alone), and
prints one JSON line per seed: the request count, the harness's per-layer
metrics, each group's idle ms per chunkset, the program spans per chunkset
and the longest idle stretches by name.  It needs the chip, as a run does.
"""
from __future__ import annotations

import argparse
import dataclasses
import fnmatch
import heapq
import json
import sys
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, trace  # noqa: E402

PROGRAM_PREFIX = "shelby."
UNSPANNED = "bench.harness"  # idle time under no span but the window's


@dataclasses.dataclass(frozen=True)
class OpGroups:
    host_gap: str  # the harness's host-gap metric for cells of this op
    count: str  # the key of the result's ``work`` that counts its chunksets
    groups: dict  # group -> the span names it sums


# a mix's op -> how its host gap splits
OPS = {
    "read": OpGroups("scan.host_gap_ms_per_chunkset", "chunkset_reads", {
        "scan.plane_schedule_ms_per_chunkset":
            ("shelby.clay.uncouple", "shelby.clay.solve", "shelby.clay.couple"),
        "scan.decode_copies_ms_per_chunkset": ("shelby.clay.decode", "shelby.range.extract"),
        "scan.gf_host_ms_per_chunkset": ("shelby.gf.call",),
        "scan.verify_ms_per_chunkset": ("shelby.rpc.verify",),
        "scan.serve_ms_per_chunkset": ("shelby.session.read",),
        "scan.unspanned_ms_per_chunkset": ("bench.*",),
    }),
    "put": OpGroups("write.host_gap_ms_per_chunkset", "chunksets_read_back", {
        "write.encode_schedule_ms_per_chunkset":
            ("shelby.clay.encode", "shelby.clay.uncouple", "shelby.clay.couple"),
        "write.encode_matmul_ms_per_chunkset": ("shelby.clay.solve",),
        "write.commit_ms_per_chunkset": ("shelby.sdk.commit", "shelby.rpc.verify"),
        "write.das_ms_per_chunkset": ("shelby.das.extend", "shelby.gf.call"),
        "write.disperse_ms_per_chunkset": ("shelby.client.put",),
        "write.unspanned_ms_per_chunkset": ("bench.*",),
    }),
}


@dataclasses.dataclass
class SpanSplit:
    window_s: float
    busy_s: float  # device busy inside the window, mean over devices
    idle_span_s: dict  # span name -> idle seconds it owned, mean over devices
    gaps: list  # (span name, seconds) of every idle stretch, longest first
    program_spans: int  # shelby.* spans that start inside the window


def _owners(lo, hi, program, harness):
    """Consecutive stretches (a, b, name) that cover [lo, hi], each owned by
    the innermost span open over it (program spans first)."""
    spans = sorted((s, e, name, i) for i, group in enumerate((program, harness))
                   for s, e, name in group if e > lo and s < hi)
    cuts = sorted({lo, hi, *(x for s, e, _, _ in spans for x in (s, e) if lo < x < hi)})
    heaps, nxt, owners = ([], []), 0, []
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(spans) and spans[nxt][0] <= a:
            s, e, name, i = spans[nxt]
            heapq.heappush(heaps[i], (-s, e - s, name, e))  # latest start, then shortest
            nxt += 1
        owner = UNSPANNED
        for heap in heaps:
            while heap and heap[0][3] <= a:  # ended: drop it once it surfaces
                heapq.heappop(heap)
            if heap:
                owner = heap[0][2]
                break
        owners.append((a, b, owner))
    return owners


def split(planes) -> SpanSplit:
    """``planes``: the ``planes`` of a ``ProfileData``, or objects shaped alike."""
    program, harness, devices = [], [], []
    for plane in planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            ops = lines.get(trace.OPS_LINE) or lines.get(trace.MODULES_LINE)
            if ops:
                devices.append(ops)
            continue
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if e.name.startswith(PROGRAM_PREFIX):
                    program.append(span)
                elif e.name.startswith(trace.SPAN_PREFIX):
                    harness.append(span)
    windows = [(s, e) for s, e, name in harness if name == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {trace.WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    harness = [sp for sp in harness if sp[2] != trace.WINDOW_SPAN]
    idles, busy_ns = [], 0.0
    for ops in devices:
        clipped = (trace._clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi) for e in ops)
        busy = trace._union([iv for iv in clipped if iv[1] > iv[0]])
        busy_ns += sum(t - s for s, t in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idles.append([(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                      if edges[i + 1] > edges[i]])
    if not devices:  # nothing ran on a device: the whole window is idle
        idles = [[(lo, hi)]]
    owners = _owners(lo, hi, program, harness)
    idle_ns: dict[str, float] = {}
    gaps = []
    for idle in idles:
        j = 0
        for s, t in idle:
            while owners[j][1] <= s:
                j += 1
            per: dict[str, float] = {}
            k = j
            while k < len(owners) and owners[k][0] < t:
                a, b, name = owners[k]
                per[name] = per.get(name, 0.0) + min(b, t) - max(a, s)
                k += 1
            j = k - 1
            for name, ns in per.items():
                idle_ns[name] = idle_ns.get(name, 0.0) + ns
            named = {k: v for k, v in per.items() if k != UNSPANNED} or per
            gaps.append((max(named, key=named.get), (t - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    n = len(idles)
    return SpanSplit(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9 / max(len(devices), 1),
                     idle_span_s={name: ns / 1e9 / n for name, ns in idle_ns.items()},
                     gaps=gaps, program_spans=sum(lo <= s < hi for s, _, _ in program))


def grouped_ms(idle_span_s: dict, groups: dict, chunksets: int) -> tuple[dict, float]:
    """Each group's idle ms per chunkset, and the ms of names no group matches."""
    out, other = {group: 0.0 for group in groups}, 0.0
    for name, s in idle_span_s.items():
        group = next((g for g, pats in groups.items()
                      if any(fnmatch.fnmatchcase(name, p) for p in pats)), None)
        if group is None:
            other += s * 1e3 / chunksets
        else:
            out[group] += s * 1e3 / chunksets
    return out, other


def measure(spec: dict, devices: list, seed: int, seconds: float, compiles,
            root: Path = ROOT) -> dict:
    """One traced run of the cell, split by span; the result line as a dict.

    A chunkset is what the result's ``work`` counts for the op: each chunkset
    read, each chunkset of an acknowledged put.  The harness's host-gap
    metric divides by its own count; where it reports one, the split must
    sum to it, or the two counts differ (reads that hit the cache decode
    nothing) and the run fails rather than print a split of another total.
    """
    from jax.profiler import ProfileData

    op = spec["mix"]["op"]
    if op not in OPS:
        raise run.Failure(f"no span groups for the op {op!r} of {spec['cell']['name']}; "
                          f"bench/span_split.py OPS has {sorted(OPS)}")
    og = OPS[op]
    kept = []

    def reduce_file(path):
        planes = list(ProfileData.from_file(path).planes)  # read twice below
        kept.append(split(planes))
        return trace.reduce(planes)

    # bench/run.py deletes its trace once reduced, and its reduction keeps no
    # program span: split the planes before that (until trace.reduce does it)
    with mock.patch.object(trace, "reduce_file", reduce_file):
        result = run.run_cell(spec, devices, seed, seconds, True, compiles, root=root,
                              t_start=time.perf_counter())
    sp = kept[0]
    chunksets = result["work"][og.count]
    if not chunksets:
        raise run.Failure(f"{spec['cell']['name']} seed {seed}: no chunksets in the window")
    gap_ms = (sp.window_s - sp.busy_s) * 1e3 / chunksets
    harness = result["metrics"].get(og.host_gap, {}).get("value")
    if harness is not None and abs(gap_ms - harness) > 1e-6 * harness:
        raise run.Failure(f"{spec['cell']['name']} seed {seed}: the split's host gap "
                          f"{gap_ms} ms over {chunksets} chunksets is not the harness's "
                          f"{og.host_gap} {harness}")
    ms, other = grouped_ms(sp.idle_span_s, og.groups, chunksets)
    return {"workload": spec["cell"]["name"], "seed": seed, "correct": result["correct"],
            "attempted": result["attempted"], "window_s": sp.window_s, "chunksets": chunksets,
            "host_gap_ms_per_chunkset": gap_ms,
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "split_ms_per_chunkset": ms, "ungrouped_ms_per_chunkset": other,
            "program_spans_per_chunkset": sp.program_spans / chunksets,
            "idle_ms_by_span": {k: v * 1e3 for k, v in sorted(sp.idle_span_s.items())},
            "idle_gaps": [[n, s] for n, s in sp.gaps[:10]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        spec = run.prepare(ROOT, args.workload)
        devices = run.find_devices(spec["cell"]["chips"])
        from repro.kernels import ops

        ops.enable_compile_cache()
        compiles = run.CompileLog()
        for seed in args.seeds:
            print(json.dumps(measure(spec, devices, seed, args.seconds, compiles)), flush=True)
    except run.Failure as e:
        print(f"span_split: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
