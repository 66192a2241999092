"""The healthy scan through the harness on the CPU: no SP is crashed, every
read still reaches the kernel, and the mix's control is caught."""
import json

import pytest


@pytest.fixture(scope="module")
def run_healthy(tiny_root, compiles):
    import jax

    from bench import faults, run

    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.healthy-scan", "config": "tiny",
                               "traffic": "healthy-scan", "chips": 1, "why": "tests"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    def go(fault=None, trace=False):
        spec = run.prepare(tiny_root, "tiny.healthy-scan")
        with faults.planted(fault):
            return run.run_cell(spec, jax.devices()[:1], 2**31 + 21, 0.3, trace, compiles,
                                root=tiny_root)

    return go


def test_healthy_scan_crashes_nothing_and_decodes_on_the_kernel(run_healthy):
    result = run_healthy(trace=True)
    assert result["correct"], result["checks"]
    assert result["work"]["crashed_sps"] == 0
    assert result["work"]["chunkset_reads_with_erased_data"] == 0
    assert result["metrics"]["scan.gf_calls_per_chunkset"]["value"] >= 1


def test_healthy_scan_control_is_caught(tiny_root, run_healthy):
    mix = json.loads((tiny_root / "bench/mixes/healthy-scan.json").read_text())
    result = run_healthy(fault=mix["control"])
    assert not result["correct"]
    assert result["checks"]["wrong_reads"]["value"] > 0
