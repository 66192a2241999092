"""A new configuration, traffic mix, plan, loop or per-layer metric is new
files and new entries in BENCHMARK.json: the harness finds each by its name,
and no file of the harness changes."""
import hashlib
import json
from pathlib import Path

import jax

from bench import run

# reads of fixed length at uniform offsets: a shape no plan of the benchmark has
PLAN = '''"""Reads of ``length_bytes`` at offsets drawn uniformly over the stored bytes."""
import itertools

import numpy as np

from bench.traffic import Request, rng_for


def requests(mix, chunkset_bytes, seed):
    blob_bytes = mix["blob_chunksets"] * chunkset_bytes
    n = mix["length_bytes"]
    cycle = mix["cycle_requests"]
    spots = (np.arange(cycle) + 0.5) / cycle * (mix["stored_blobs"] * (blob_bytes - n))
    order = rng_for(seed, 3).permutation(spots.astype(np.int64))
    return (Request(blob=int(o) // (blob_bytes - n), offset=int(o) % (blob_bytes - n), length=n)
            for o in itertools.cycle(order))
'''

# two clients taking turns: a loop no file of the benchmark has
LOOP = '''"""Two clients sending in turn, each its next request when its last returned."""
from bench.traffic import Done, call


def run(requests, issue, name, seconds, clock, span):
    done = []
    t0 = clock()
    for i, req in enumerate(requests):
        start = clock() - t0
        with span(f"{name}.{i % 2}"):
            answer, error = call(issue, req)
        done.append(Done(req, start, clock() - t0, answer, error))
        if done[-1].end_s >= seconds:
            break
    return t0, done
'''

READER = '''"""Bytes per request in the window."""


def read(r):
    return sum(len(d.answer) for d in r.done) / len(r.done) if r.done else None
'''


def _digests(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path, compiles, make_checkout):
    root = make_checkout(tmp_path)
    before = _digests(root)
    config = json.loads((root / "bench/configs/tiny.json").read_text())
    config.update(name="tiny-wide", cache_chunksets_per_node=2, num_sps=9)
    (root / "bench/configs/tiny-wide.json").write_text(json.dumps(config))
    mix = json.loads((root / "bench/mixes/degraded-scan.json").read_text())
    mix.update(stored_blobs=2, blob_chunksets=3, erased_data_chunks=1)
    (root / "bench/mixes/short-scan.json").write_text(json.dumps(mix))
    (root / "bench/metrics/scan.bytes_per_request.py").write_text(READER)

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "tests",
                             "file": "bench/configs/tiny-wide.json", "reduced": [],
                             "why": "tests"})
    bench["workloads"].append({"name": "tiny-wide.short-scan", "config": "tiny-wide",
                               "traffic": "short-scan", "chips": 1, "why": "tests"})
    bench["end_to_end"][0]["workloads"].append("tiny-wide.short-scan")
    bench["per_layer"].append({"name": "scan.bytes_per_request", "unit": "B", "better": "higher",
                               "source": "host_clock", "layer": "client session",
                               "moves": "read_MBps", "workloads": ["tiny-wide.short-scan"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    added = set(_digests(root)) - set(before)
    assert {a for a in added} == {Path("bench/configs/tiny-wide.json"),
                                  Path("bench/mixes/short-scan.json"),
                                  Path("bench/metrics/scan.bytes_per_request.py")}
    assert all(_digests(root)[p] == d for p, d in before.items())  # nothing else edited

    spec = run.prepare(root, "tiny-wide.short-scan")
    assert spec["config"]["num_sps"] == 9 and spec["mix"]["blob_chunksets"] == 3
    result = run.run_cell(spec, jax.devices()[:1], 77, 0.3, True, compiles, root=root)
    assert result["correct"], result["checks"]
    assert result["metrics"]["scan.bytes_per_request"]["value"] == 4 * 8 * 2048  # one chunkset: k * alpha * w
    result = run.run_cell(spec, jax.devices()[:1], 78, 0.3, False, compiles, root=root)
    assert set(result["metrics"]) == {"read_MBps", "setup_s"}


def test_new_plan_and_loop_of_a_new_shape_are_found_by_name(tmp_path, compiles, make_checkout):
    root = make_checkout(tmp_path)
    before = _digests(root)
    mix = json.loads((root / "bench/mixes/degraded-scan.json").read_text())
    mix.update(plan="uniform_fixed", loop="two_turns", length_bytes=(2048 + 1) * 4,
               cycle_requests=32)
    (root / "bench/mixes/loader.json").write_text(json.dumps(mix))
    (root / "bench/plans/uniform_fixed.py").write_text(PLAN)
    (root / "bench/loops/two_turns.py").write_text(LOOP)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.loader", "config": "tiny", "traffic": "loader",
                               "chips": 1, "why": "tests"})
    bench["end_to_end"][0]["workloads"].append("tiny.loader")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert set(_digests(root)) - set(before) == {Path("bench/mixes/loader.json"),
                                                 Path("bench/plans/uniform_fixed.py"),
                                                 Path("bench/loops/two_turns.py")}
    assert all(_digests(root)[p] == d for p, d in before.items())

    spec = run.prepare(root, "tiny.loader")
    result = run.run_cell(spec, jax.devices()[:1], 2**31 + 99, 0.3, False, compiles, root=root)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2
    assert result["work"]["chunkset_reads"] >= result["attempted"]
    assert set(result["metrics"]) == {"read_MBps", "setup_s"}
