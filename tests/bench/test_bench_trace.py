"""The reduction from a profiler trace to busy time, program time and idle gaps."""
from collections import namedtuple
from pathlib import Path

import pytest

from bench import trace

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")

RECORDED = Path(__file__).resolve().parent / "data" / "gf_matmul_v5e.xplane.pb"


def _ev(name, start, end):
    return Event(name, start, end - start)


def _planes():
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ev("jit_gf_matmul(1)", 100, 300), _ev("jit_gf_matmul(2)", 500, 600),
                             _ev("jit_other(3)", 50, 80)]),
        Line("XLA Ops", [_ev("fusion", 100, 150), _ev("gf_matmul_kernel", 140, 300),
                         _ev("gf_matmul_kernel", 500, 600), _ev("copy", 50, 80)]),
    ])
    host = Plane("/host:CPU", [Line("python", [
        _ev("bench.window", 90, 1000), _ev("bench.read", 90, 450), _ev("bench.put", 450, 1000),
        _ev("PjitFunction(gf_matmul)", 95, 99)])])
    idle_chip = Plane("/device:TPU:1", [Line("XLA Ops", [])])
    return [host, device, idle_chip]


def test_reduce_counts_busy_as_a_union_inside_the_window():
    s = trace.reduce(_planes())
    assert s.devices == 1  # a chip with no operation is not one of the chips used
    assert s.window_s == pytest.approx(910e-9)
    assert s.busy_s == pytest.approx(300e-9)  # [100,300] and [500,600]; [50,80] is outside
    assert s.program_s == pytest.approx({"jit_gf_matmul": 300e-9})
    assert s.program_seconds("gf_matmul") == pytest.approx(300e-9)
    assert s.op_s["gf_matmul_kernel"] == pytest.approx(260e-9)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    s = trace.reduce(_planes())
    assert [name for name, _ in s.gaps] == ["bench.put", "bench.read", "bench.read"]
    assert [g for _, g in s.gaps] == pytest.approx([400e-9, 200e-9, 10e-9])
    b = s.breakdown(top=2)
    assert b["idle_gaps"] == [["bench.put", pytest.approx(400e-9)],
                              ["bench.read", pytest.approx(200e-9)]]
    assert b["device_ops"][0] == ["gf_matmul_kernel", pytest.approx(260e-9)]


def test_a_trace_without_the_window_span_is_refused():
    planes = [p for p in _planes() if p.name != "/host:CPU"]
    with pytest.raises(ValueError):
        trace.reduce(planes)


def test_reduce_a_trace_recorded_on_a_v5e():
    """Two gf_matmul calls, (6,12)x(12,1048896) and (6,12)x(12,65536), each
    under a ``bench.read`` span inside ``bench.window``, recorded on one chip."""
    s = trace.reduce_file(str(RECORDED))
    assert s.devices == 1
    assert s.window_s == pytest.approx(24.235038e-3, rel=1e-6)
    # the two jit_gf_matmul programs: 1948892 ns and 119247 ns on the device
    assert s.program_s == pytest.approx({"jit_gf_matmul": 2068139e-9}, rel=1e-6)
    assert "%gf_matmul.1" in s.op_s and "%pad.0" in s.op_s
    assert 2.0e-3 < s.busy_s <= s.program_seconds("gf_matmul") + 1e-6
    assert sum(g for _, g in s.gaps) + s.busy_s == pytest.approx(s.window_s, rel=1e-6)
    assert {name for name, _ in s.gaps} <= {"bench.read", "bench.harness"}
