"""The split of a window's device-idle time by the program's spans
(``bench/span_split.py``): on planes made by hand, on a trace recorded on a
v5e, on a profile of ``repro.spans`` recorded here, and on traced tiny runs."""
import subprocess
import sys
from collections import namedtuple
from pathlib import Path

import pytest

from bench import span_split, trace

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")

REPO = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).resolve().parent / "data" / "gf_matmul_v5e.xplane.pb"


def _ev(name, start, end):
    return Event(name, start, end - start)


def _planes(*extra):
    """Busy [100,300] and [500,600] in the window [90,1000]; a read whose
    program spans nest four deep, then a put."""
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ev("jit_gf_matmul(1)", 100, 300), _ev("jit_gf_matmul(2)", 500, 600)]),
        Line("XLA Ops", [_ev("gf_matmul_kernel", 100, 300), _ev("gf_matmul_kernel", 500, 600)]),
    ])
    host = Plane("/host:CPU", [Line("python", [
        _ev("bench.window", 90, 1000), _ev("bench.read", 90, 450), _ev("bench.put", 450, 1000),
        _ev("shelby.session.read", 94, 440), _ev("shelby.clay.decode", 120, 410),
        _ev("shelby.clay.solve", 150, 350), _ev("shelby.gf.call", 160, 320),
        _ev("PjitFunction(gf_matmul)", 161, 170), *extra])])
    return [host, device]


def test_idle_time_goes_to_the_innermost_open_span():
    s = span_split.split(_planes())
    assert s.idle_span_s == pytest.approx({
        "bench.read": 14e-9,  # [90,94] and [440,450]: no program span open
        "shelby.session.read": 36e-9,  # [94,100] and [410,440]
        "shelby.gf.call": 20e-9,  # [300,320]: the device finished before the call returned
        "shelby.clay.solve": 30e-9,
        "shelby.clay.decode": 60e-9,
        "bench.put": 450e-9,
    })
    assert s.program_spans == 4


def test_the_split_sums_to_the_host_gap_of_the_reduction():
    s, whole = span_split.split(_planes()), trace.reduce(_planes())
    assert (s.window_s, s.busy_s) == pytest.approx((whole.window_s, whole.busy_s))
    assert sum(s.idle_span_s.values()) == pytest.approx(whole.window_s - whole.busy_s)


def test_each_gap_is_named_by_the_span_that_owned_most_of_it():
    s = span_split.split(_planes())
    assert s.gaps == [("bench.put", pytest.approx(400e-9)),
                      ("shelby.clay.decode", pytest.approx(200e-9)),
                      ("shelby.session.read", pytest.approx(10e-9))]


def test_ties_go_to_the_shorter_span_and_no_span_means_the_harness():
    # two program spans opened together in the put's gap; after the put, none
    planes = _planes(_ev("shelby.client.put", 600, 800), _ev("shelby.clay.encode", 600, 700))
    planes[0].lines[0].events[2] = _ev("bench.put", 450, 900)
    s = span_split.split(planes)
    assert s.idle_span_s["shelby.clay.encode"] == pytest.approx(100e-9)
    assert s.idle_span_s["shelby.client.put"] == pytest.approx(100e-9)
    assert s.idle_span_s["bench.harness"] == pytest.approx(100e-9)  # [900,1000]
    assert s.idle_span_s["bench.put"] == pytest.approx(150e-9)  # [450,500], [800,900]


def test_with_harness_spans_alone_the_gaps_are_the_reductions():
    from jax.profiler import ProfileData

    s = span_split.split(ProfileData.from_file(str(RECORDED)).planes)
    whole = trace.reduce_file(str(RECORDED))
    assert s.gaps == whole.gaps
    assert s.busy_s == pytest.approx(whole.busy_s, rel=1e-9)
    assert sum(s.idle_span_s.values()) == pytest.approx(whole.window_s - whole.busy_s, rel=1e-9)
    assert set(s.idle_span_s) <= {"bench.read", "bench.harness"} and s.program_spans == 0


def test_program_spans_with_args_keep_clean_names(tmp_path):
    """Recorded here: the profiler keeps a span's args out of its name."""
    import jax
    from jax.profiler import ProfileData

    from repro.spans import span

    with jax.profiler.trace(str(tmp_path)):
        with span("bench.window"), span("bench.read"):
            with span("shelby.session.read", blob=3, offset=0, length=4096):
                with span("shelby.rpc.verify"):
                    sum(range(20000))
                sum(range(20000))
    s = span_split.split(ProfileData.from_file(trace.xplane_file(str(tmp_path))).planes)
    assert {"shelby.session.read", "shelby.rpc.verify"} <= set(s.idle_span_s)
    assert set(s.idle_span_s) <= {"bench.harness", "bench.read", "shelby.session.read",
                                  "shelby.rpc.verify"}
    assert s.busy_s == 0  # no device plane on the CPU: the whole window is idle
    assert sum(s.idle_span_s.values()) == pytest.approx(s.window_s, rel=1e-6)


EXPECTED_SPANS = {
    "read": {"shelby.session.read", "shelby.rpc.verify", "shelby.clay.decode",
                      "shelby.clay.uncouple", "shelby.clay.solve", "shelby.clay.couple",
                      "shelby.gf.call", "shelby.range.extract"},
    "put": {"shelby.client.put", "shelby.clay.encode", "shelby.clay.uncouple",
                   "shelby.clay.solve", "shelby.clay.couple", "shelby.sdk.commit",
                   "shelby.rpc.verify", "shelby.das.extend", "shelby.gf.call"},
}


def _check_split(line, op):
    assert line["correct"] and line["attempted"] > 0
    og = span_split.OPS[op]
    split = line["split_ms_per_chunkset"]
    assert set(split) == set(og.groups)
    assert all(v >= 0 for v in split.values())
    assert line["ungrouped_ms_per_chunkset"] == 0
    assert sum(split.values()) == pytest.approx(line["host_gap_ms_per_chunkset"], rel=1e-6)
    assert EXPECTED_SPANS[op] <= set(line["idle_ms_by_span"])
    assert line["program_spans_per_chunkset"] > 0


@pytest.mark.parametrize("mix", ["degraded-scan", "put-stream"])
def test_traced_tiny_run_is_split_into_the_host_gap(tiny_root, compiles, mix):
    import jax

    from bench import run

    spec = run.prepare(tiny_root, f"tiny.{mix}")
    line = span_split.measure(spec, jax.devices()[:1], 2**31 + 11, 0.4, compiles,
                              root=tiny_root)
    _check_split(line, spec["mix"]["op"])
    og = span_split.OPS[spec["mix"]["op"]]
    assert line["host_gap_ms_per_chunkset"] == pytest.approx(line["metrics"][og.host_gap],
                                                             rel=1e-6)


def test_a_new_mix_of_a_known_op_is_split_with_no_edit(make_checkout, compiles, tmp_path):
    """A mix the groups never named, added as data: the split follows its op."""
    import json

    import jax

    from bench import run

    root = make_checkout(tmp_path)
    mix = json.loads((root / "bench/mixes/degraded-scan.json").read_text())
    mix.update(stored_blobs=5, blob_chunksets=4)
    (root / "bench/mixes/wide-scan.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.wide-scan", "config": "tiny",
                               "traffic": "wide-scan", "chips": 1, "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    tool = "bench/span_split.py"
    assert (root / tool).read_bytes() == (REPO / tool).read_bytes()
    spec = run.prepare(root, "tiny.wide-scan")
    line = span_split.measure(spec, jax.devices()[:1], 2**31 + 13, 0.4, compiles, root=root)
    _check_split(line, "read")
    assert "scan.host_gap_ms_per_chunkset" not in line["metrics"]  # no metric lists the cell


def test_an_op_with_no_groups_fails_before_the_run(tiny_root, compiles):
    from bench import run

    spec = run.prepare(tiny_root, "tiny.degraded-scan")
    spec["mix"] = dict(spec["mix"], op="scrub")
    with pytest.raises(run.Failure, match="no span groups for the op 'scrub'"):
        span_split.measure(spec, [], 2**31 + 17, 0.4, compiles, root=tiny_root)


def test_the_command_refuses_to_run_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench/span_split.py", "--workload", "shelby-10-6.degraded-scan",
         "--seeds", "5", "--seconds", "1"], cwd=REPO, capture_output=True, text=True,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path),
             "TMPDIR": str(tmp_path)}, timeout=120)
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
