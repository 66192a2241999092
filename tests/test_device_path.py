"""The one place that picks host or device decode, its counters, the
compile cache, and the chip smoke's refusal to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro.core import gf
from repro.kernels import ops
from repro.storage.rpc import RPCNode

ROOT = Path(__file__).resolve().parent.parent


def _run(code_or_args, env_extra=None, unset=()):
    env = {k: v for k, v in os.environ.items() if k not in unset}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **(env_extra or {}))
    return subprocess.run([sys.executable, *code_or_args], capture_output=True, text=True,
                          env=env, cwd=ROOT, timeout=300)


def test_resolve_binds_the_kernel_to_a_device_and_counts_its_traffic():
    dev = jax.devices()[0]
    assert ops.resolve_decode_matmul("auto", dev) is None  # a CPU device: numpy
    matmul = ops.resolve_decode_matmul("pallas", dev)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, (2, 3), dtype=np.uint8)
    b = rng.integers(0, 256, (3, 100), dtype=np.uint8)
    ops.reset_gf_traffic()
    assert np.array_equal(matmul(a, b), gf.matmul_np(a, b))
    assert ops.gf_traffic() == {dev: (1, 300)}
    assert ops.gf_compilations() >= 1
    ops.reset_gf_traffic()
    assert ops.gf_traffic() == {}


def test_rpc_node_defaults_to_the_platform_choice(cluster):
    contract, sps, rpc, client = cluster
    assert rpc.decode_matmul is None  # numpy on the CPU, as before
    node = RPCNode("rpcd", contract, sps, rpc.layout, decode_matmul="pallas",
                   device=jax.devices()[0])
    assert node.decode_matmul.keywords == {"device": jax.devices()[0]}


def test_compile_cache_uses_the_env_dir_else_the_checkout(tmp_path):
    script = ("from repro.kernels import ops; import jax, jax.numpy as jnp; "
              "print(ops.enable_compile_cache()); "
              "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()")
    res = _run(["-c", script], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.split()[0] == str(tmp_path)
    assert any(tmp_path.iterdir())  # the compile above was kept, however short
    res = _run(["-c", "from repro.kernels import ops; print(ops.enable_compile_cache())"],
               unset=("JAX_COMPILATION_CACHE_DIR",))
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.split()[0] == str(ROOT / ".jax_cache")


def test_chip_smoke_refuses_to_run_without_a_tpu():
    res = _run([str(ROOT / "chip_smoke.py")])
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert '"ok"' not in res.stdout  # no result line
