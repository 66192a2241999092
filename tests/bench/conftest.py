"""Fixtures for the benchmark's CPU tests: a checkout with a tiny cell of each mix.

The harness lives outside ``src``; these tests import it by the repository's
path.  Nothing here touches a TPU: the harness's look for a chip is replaced
by JAX's first CPU device, and the GF kernel runs in interpret mode.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

MIXES = ("degraded-scan", "put-stream")


def tiny_checkout(dest: Path) -> Path:
    """A checkout whose BENCHMARK.json holds a (4,2) Clay cell of each mix."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "src").symlink_to(REPO / "src")
    tiny = json.loads((REPO / "bench/configs/shelby-10-6.json").read_text())
    tiny.update(name="tiny", k=4, m=2, d=5, n=6, alpha=8, w=2048,
                chunkset_bytes_target=64 * 1024, num_sps=8, decode_matmul="pallas")
    tiny["guarantees"] = dict(tiny["guarantees"], acked_puts_survive_sp_failures=2)
    (dest / "bench/configs/tiny.json").write_text(json.dumps(tiny))
    put = json.loads((dest / "bench/mixes/put-stream.json").read_text())
    put["put_bytes"] = 100_000
    (dest / "bench/mixes/put-stream.json").write_text(json.dumps(put))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "a (4,2) Clay layout for the tests",
                             "file": "bench/configs/tiny.json", "reduced": [], "why": "tests"})
    for mix in MIXES:
        bench["workloads"].append({"name": f"tiny.{mix}", "config": "tiny", "traffic": mix,
                                   "chips": 1, "why": "tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += ["tiny." + w.split(".", 1)[1] for w in metric["workloads"]]
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture
def make_checkout():
    return tiny_checkout


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def compiles():
    from bench import run

    return run.CompileLog()


@pytest.fixture(scope="module")
def run_tiny(tiny_root, compiles):
    """Run one tiny cell through the harness on the CPU, with a fault planted."""
    import jax

    from bench import faults, run

    def go(mix, fault=None, seed=2**31 + 7, seconds=0.4, trace=False):
        spec = run.prepare(tiny_root, f"tiny.{mix}")
        with faults.planted(fault):
            result = run.run_cell(spec, jax.devices()[:1], seed, seconds, trace, compiles,
                                  root=tiny_root)
        return result

    return go
