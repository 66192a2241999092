#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <config>.<mix> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/mixes/<mix>.json``) are found by name through
``BENCHMARK.json`` at the root of the checkout, and the mix names the op,
the plan and the loop that carry it out (``bench/parts.py``).  The run
builds the deployment, lets the op set up (store the working set from the
seed, crash SPs, warm every kernel shape the window will use), then
measures for ``--seconds`` on the host clock.  Every read and every put
returns host bytes, so the time includes the device's work.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
window under the JAX profiler and prints the per-layer metrics, each from
its reader in ``bench/metrics/``.  The last line of standard output is one
JSON object; the lines before it on standard error end with every number
the correctness check compared, each beside its limit.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result: it never falls back to the CPU.  JAX's
persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # noqa: E402 -- set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, deploy, parts, reading, traffic  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

MB = 1e6


class Failure(Exception):
    """The run cannot stand: exit non-zero and print no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding things by name ----------------------------------------------------------
def load_json(path: Path) -> dict:
    if not path.is_file():
        raise Failure(f"{path} is missing")
    return json.loads(path.read_text())


def load_cell(root: Path, workload: str) -> dict:
    """The benchmark, the cell, its configuration and its mix."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Failure(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(root / "bench" / "mixes" / f"{cell['traffic']}.json")
    return {"bench": bench, "cell": cell, "config": config, "mix": mix}


def reported(spec: dict, trace: bool) -> list[dict]:
    """The metrics this cell prints: its end-to-end ones, or its per-layer ones."""
    name = spec["cell"]["name"]
    e2e = [m for m in spec["bench"]["end_to_end"] if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["bench"]["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]


# -- the device ---------------------------------------------------------------------
def find_devices(chips: int) -> list:
    """The first ``chips`` TPU chips JAX sees; anything else is a failure."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Failure(f"no TPU found: JAX's devices are {devices}")
    if len(devices) < chips:
        raise Failure(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


class CompileLog:
    """Counts XLA compiles (cache loads included) and persistent-cache hits."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profiled(enabled: bool):
    """Run the block under the JAX profiler; yield a callable that reduces
    the trace once the block has ended."""
    if not enabled:
        yield lambda: None
        return
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host Python is the work measured; do not slow it
    opts.host_tracer_level = 1  # the benchmark's spans
    result = {}
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield lambda: result.get("summary")
    finally:
        jax.profiler.stop_trace()
        try:
            result["summary"] = trace_mod.reduce_file(trace_mod.xplane_file(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)


def counters(dep: deploy.Deployment, compiles: CompileLog) -> dict:
    from repro.kernels import ops

    nodes = dep.fleet.rpcs
    return {
        "chunksets_decoded": sum(n.stats.chunksets_decoded for n in nodes),
        "chunksets_decoded_on_host": sum(n.stats.chunksets_decoded_on_host for n in nodes),
        "cache_hits": sum(n.stats.cache_hits for n in nodes),
        "chunkset_reads": dep.fleet.chunkset_reads,
        "gf_calls": sum(calls for calls, _ in ops.gf_traffic().values()),
        "compiles": compiles.compiles,
        "kernel_calls_logged": len(dep.kernels.shapes),
    }


# -- end-to-end metrics ----------------------------------------------------------------
def _ok(done):
    return [d for d in done if d.error is None]


END_TO_END = {
    "read_MBps": lambda done, window_s, setup_s: sum(len(d.answer) for d in _ok(done)) / window_s / MB,
    "write_MBps": lambda done, window_s, setup_s: sum(
        d.request.length for d in _ok(done)) / window_s / MB,
    "setup_s": lambda done, window_s, setup_s: setup_s,
}


# -- one run --------------------------------------------------------------------------
def run_cell(spec: dict, devices: list, seed: int, seconds: float, trace: bool,
             compiles: CompileLog, root: Path = ROOT, t_start: float = T_START) -> dict:
    """Set up, measure, check; return the result line as a dict."""
    config, mix, cell = spec["config"], spec["mix"], spec["cell"]
    log(f"process start to deployment {time.perf_counter() - t_start} s")
    dep = deploy.build(config, devices)
    cs = dep.layout.chunkset_bytes
    t_data = time.perf_counter()
    op = parts.load(root, "ops", mix["op"]).Op(dep, mix, seed, log)
    requests = traffic.plan(root, mix, cs, seed)
    log(f"op set-up {time.perf_counter() - t_data} s")
    before = counters(dep, compiles)
    with profiled(trace) as read_trace:
        with span(trace_mod.WINDOW_SPAN):
            t0, done = traffic.run_loop(root, mix, requests, op.issue, op.span, seconds,
                                        time.perf_counter, span)
    window_s = done[-1].end_s
    setup_s = t0 - t_start
    after = counters(dep, compiles)
    delta = {k: after[k] - before[k] for k in after}
    shapes = dep.kernels.shapes[before["kernel_calls_logged"]:after["kernel_calls_logged"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    took = sorted(d.end_s - d.start_s for d in done)
    log(f"window {window_s} s, {len(done)} requests, each {took[0]}..{took[len(took) // 2]}.."
        f"{took[-1]} s (least, median, most); counters over the window {delta}")
    errors = [d.error for d in done if d.error is not None]
    if errors:
        log(f"{len(errors)} requests failed; the first: {errors[0]}")
    if delta["chunkset_reads"]:
        log(f"hot-cache hit rate {100.0 * delta['cache_hits'] / delta['chunkset_reads']} % "
            f"of {delta['chunkset_reads']} chunkset lookups")

    checks = check.Checks()
    op.check(checks, done)
    on_host = sum(n.stats.chunksets_decoded_on_host for n in dep.fleet.rpcs)
    if config["guarantees"]["decode_on_device"]:
        checks.add("host_decodes", on_host, 0)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics = {}
    wrong = checks.items.get("wrong_reads", {}).get("value", 0)
    result = {"correct": checks.correct, "attempted": len(done),
              "failed": sum(d.error is not None for d in done) + wrong}
    summary = read_trace()
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        r = reading.Reading(done=done, counters=delta, kernel_shapes=shapes,
                            trace=summary, chunksets=op.chunksets(done, delta), root=root,
                            device_kind=devices[0].device_kind)
        for m in reported(spec, trace=True):
            value = parts.load(root, "metrics", m["name"]).read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
    else:
        for m in reported(spec, trace=False):
            if m["name"] not in END_TO_END:
                raise Failure(f"no formula for the end-to-end metric {m['name']!r}")
            metrics[m["name"]] = {"value": END_TO_END[m["name"]](done, window_s, setup_s),
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device, work=op.work(done), checks=checks.items)
    log(f"cell {cell['name']} seed {seed}: setup {setup_s} s, compiles {compiles.compiles} "
        f"({compiles.cache_hits} from the persistent cache); work {result['work']}")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(root: Path, workload: str):
    """The cell's files and the program, before JAX is touched."""
    spec = load_cell(root, workload)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Failure(f"the program is not at {src}: run from a checkout of the repo")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    return spec


def emit(result: dict) -> None:
    """The checks last on standard error; the result line last on standard output."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse_args(argv)
    try:
        spec = prepare(root, args.workload)
        devices = find_devices(spec["cell"]["chips"])
        log(f"process start to JAX's devices {time.perf_counter() - T_START} s")
        from repro.kernels import ops

        cache_dir = ops.enable_compile_cache()
        log(f"device {devices[0].platform} {devices[0].device_kind} x{len(devices)}, "
            f"compile cache {cache_dir}")
        result = run_cell(spec, devices, args.seed, args.seconds, bool(args.trace),
                          CompileLog(), root=root)
    except Failure as e:
        log(f"bench: {e}")
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
