"""The program's spans (``repro.spans``): recorded under the profiler, each
nested in its parent, and no JAX import on the numpy-only paths."""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = Path(__file__).resolve().parents[1] / "src"


def _record(tmp_path, work):
    """Run ``work`` under the profiler; return the ``shelby.*`` spans as
    (name, start_ns, end_ns)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        work()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes for line in plane.lines
            for e in line.events if e.name.startswith("shelby.")]


def _nested(spans, child, parent):
    """Every ``child`` span lies inside some ``parent`` span."""
    parents = [(s, e) for n, s, e in spans if n == parent]
    kids = [(s, e) for n, s, e in spans if n == child]
    return bool(kids) and all(any(ps <= s and e <= pe for ps, pe in parents) for s, e in kids)


def test_clay_decode_batch_records_the_plane_schedule_and_the_kernel_call(tmp_path):
    from repro.core.clay import ClayCode
    from repro.kernels import ops

    code = ClayCode(k=4, m=2)
    rng = np.random.default_rng(7)
    cw = code.encode(rng.integers(0, 256, (code.k, code.alpha, 64), dtype=np.uint8))
    shards = {i: cw[i] for i in (1, 2, 3, 4)}  # data chunk 0 and parity 5 erased
    out = []
    spans = _record(tmp_path, lambda: out.extend(code.decode_batch([shards],
                                                                  matmul=ops.gf_matmul_np)))
    np.testing.assert_array_equal(out[0], cw)
    names = {n for n, _, _ in spans}
    assert {"shelby.clay.decode", "shelby.clay.uncouple", "shelby.clay.solve",
            "shelby.clay.couple", "shelby.gf.call"} <= names
    for step in ("uncouple", "solve", "couple"):
        assert _nested(spans, f"shelby.clay.{step}", "shelby.clay.decode")
    assert _nested(spans, "shelby.gf.call", "shelby.clay.solve")
    # one span per step of each IS group, never one per plane
    groups = sum(n == "shelby.clay.solve" for n, _, _ in spans)
    assert sum(n == "shelby.clay.uncouple" for n, _, _ in spans) == groups < code.alpha


def test_client_put_and_read_record_the_write_and_read_paths(tmp_path):
    from repro.launch.train import build_cluster

    _, _, _, client = build_cluster(num_sps=8)
    data = np.random.default_rng(3).integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    metas = []

    def work():
        meta = client.put(data)
        metas.append(meta)
        assert client.read(meta.blob_id, 1000, 5000).data == data[1000:6000]

    spans = _record(tmp_path, work)
    (put_end,) = [e for n, _, e in spans if n == "shelby.client.put"]
    during_put = [sp for sp in spans if sp[1] < put_end]
    after_put = [sp for sp in spans if sp[1] >= put_end]
    for child in ("shelby.clay.encode", "shelby.sdk.commit", "shelby.rpc.verify",
                  "shelby.das.extend"):
        assert _nested(during_put, child, "shelby.client.put"), child
    assert _nested(during_put, "shelby.clay.solve", "shelby.clay.encode")
    for child in ("shelby.rpc.verify", "shelby.clay.decode", "shelby.range.extract"):
        assert _nested(after_put, child, "shelby.session.read"), child
    # one commitment span per chunkset, one verify per chunk stored
    chunksets = metas[0].num_chunksets
    assert sum(n == "shelby.sdk.commit" for n, _, _ in during_put) == chunksets
    assert sum(n == "shelby.rpc.verify" for n, _, _ in during_put) == chunksets * client.layout.n


@pytest.mark.parametrize("args", [{}, {"blob": 3, "offset": 0, "length": 4096}])
def test_a_span_records_nothing_and_imports_no_jax_where_jax_is_not_loaded(args):
    code = f"""
import sys
import numpy as np
from repro.core.clay import ClayCode
from repro.spans import span
with span("shelby.test", **{args!r}):
    c = ClayCode(k=4, m=2)
    c.encode(np.zeros((4, c.alpha, 8), np.uint8))
assert "jax" not in sys.modules, "a span imported JAX"
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"}, timeout=120)
    assert res.returncode == 0, res.stderr
