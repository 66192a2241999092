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


def _logged_matmul(calls):
    def matmul(a, b):
        calls.append((a.shape, b.shape))
        return gf.matmul_np(a, b)

    return matmul


def test_prepare_encodes_through_the_node_matmul(cluster, rng):
    contract, sps, rpc, client = cluster
    data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
    calls = []
    rpc.decode_matmul = _logged_matmul(calls)
    prep = client.prepare(data)
    chunksets = len(prep.encoded_chunksets)
    assert chunksets == 3
    lay = client.layout
    assert calls and all(a == (lay.m, lay.code.N - lay.m) for a, _ in calls)
    assert client.stats.chunksets_encoded == chunksets
    assert client.stats.chunksets_encoded_on_host == 0
    meta = client.put(data)
    assert client.stats.chunksets_encoded == 2 * chunksets
    assert client.stats.chunksets_encoded_on_host == 0
    assert client._encoder() is client._encoder()  # bound once, not per put
    assert client.get(meta.blob_id) == data


def test_prepare_without_a_node_matmul_encodes_on_host_identically(cluster, rng):
    contract, sps, rpc, client = cluster
    data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
    assert rpc.decode_matmul is None
    on_host = client.prepare(data)
    assert client.stats.chunksets_encoded_on_host == len(on_host.encoded_chunksets) == 3
    assert client.stats.chunksets_encoded == 3
    calls = []
    rpc.decode_matmul = _logged_matmul(calls)
    on_device = client.prepare(data)
    assert calls  # the code was bound anew to the node's new matmul
    for got, want in zip(on_device.encoded_chunksets, on_host.encoded_chunksets, strict=True):
        np.testing.assert_array_equal(got, want)
    assert (on_device.size_bytes, on_device.chunk_roots, on_device.chunk_num_samples,
            on_device.chunkset_roots, on_device.blob_root) == (
        on_host.size_bytes, on_host.chunk_roots, on_host.chunk_num_samples,
        on_host.chunkset_roots, on_host.blob_root)
    assert client.stats.chunksets_encoded_on_host == 3
    assert client.stats.chunksets_encoded == 6


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
