"""The command as the benchmark runs it: it never falls back to the CPU, and
it needs the program beside it."""
import json
from pathlib import Path
import shutil
import subprocess
import sys

REPO = Path(__file__).resolve().parents[2]

ARGS = ["--workload", "shelby-10-6.degraded-scan", "--seed", "5", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(cwd)}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_exits_non_zero_without_a_tpu(tmp_path):
    proc = _run(REPO, {"TMPDIR": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_exits_non_zero_with_only_the_benchmarks_files(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
