"""Compile the main-path kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(tiling mismatches, VMEM overflow), which interpret-mode tests cannot see.
The topology is described inside a fixture, never at import, because only
one process at a time may load the TPU library.  The persistent compile
cache is off around the compiles: an entry written without a chip cannot
be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gf_bitmatmul as _gb
from repro.kernels import gf_matmul as _gf
from repro.kernels import sample_hash as _sh


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes_dtypes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes_dtypes]
    return fn.lower(*args, interpret=False, **static).compile()


@pytest.mark.parametrize("m,k,n", [
    (2, 16, 699264),  # (10,6) Clay, 2 erasures, one IS group of one chunkset
    (6, 16, 1048464),  # 6 unknowns over a whole chunkset's planes
    (6, 12, 1048896),  # a k-of-n read (6 erasures, 12 known nodes) and the (10,6) encode
    (4, 16, 655360),  # the (20,16) encode: 4 parities from 16 known nodes, alpha=1024, w=640
    (4, 4, 4096),  # the DAS extension's (4,4) square
])
def test_gf_matmul_compiles_for_v5e(one_chip, m, k, n):
    compiled = _compile(_gf.gf_matmul, one_chip, ((m, k), jnp.uint8), ((k, n), jnp.uint8))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 65536),  # the 128 x 128 DAS square's column extension: k x (k x 512) bytes
    (128, 128, 131072),  # its row extension: k x (2k x 512) bytes
])
def test_gf_bitmatmul_compiles_for_v5e(one_chip, m, k, n):
    compiled = _compile(_gb.gf_bitmatmul, one_chip, ((m, k), jnp.uint8), ((k, n), jnp.uint8))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("leaves", [4096, 1000])
def test_sample_hash_compiles_for_v5e_at_1kib_leaves(one_chip, leaves):
    compiled = _compile(_sh.sample_hash, one_chip, ((leaves, 256), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()
