"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the optional dep
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import gf
from repro.kernels import ops


@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (2, 3, 64), (6, 10, 1000), (16, 18, 4096), (6, 16, 2049),
    (18, 18, 5000), (1, 18, 128), (8, 4, 3),
])
def test_gf_matmul_shapes(m, k, n):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    a = rng.integers(0, 256, (m, k), dtype=np.uint8)
    b = rng.integers(0, 256, (k, n), dtype=np.uint8)
    out = np.asarray(ops.gf_matmul(a, b))
    ref = np.asarray(ops.gf_matmul_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(out, gf.matmul_np(a, b))


def test_gf_matmul_lowers_to_a_module_named_jit_gf_matmul():
    """The benchmark finds the kernel's device time in a trace by the jitted
    program's name (``gf_matmul_roofline``): a rename would empty it."""
    from repro.kernels import gf_matmul as _gf

    a, b = jnp.zeros((2, 4), jnp.uint8), jnp.zeros((4, 2048), jnp.uint8)
    text = _gf.gf_matmul.lower(a, b, interpret=True).as_text()
    assert text.startswith("module @jit_gf_matmul ")


@pytest.mark.parametrize("block_n", [8, 128, 2048])
def test_gf_matmul_block_sizes(block_n):
    rng = np.random.default_rng(block_n)
    a = rng.integers(0, 256, (6, 10), dtype=np.uint8)
    b = rng.integers(0, 256, (10, 777), dtype=np.uint8)
    out = np.asarray(ops.gf_matmul(a, b, block_n=block_n))
    np.testing.assert_array_equal(out, gf.matmul_np(a, b))


@given(st.integers(1, 12), st.integers(1, 12), st.integers(1, 300), st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
def test_gf_matmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, (m, k), dtype=np.uint8)
    b = rng.integers(0, 256, (k, n), dtype=np.uint8)
    np.testing.assert_array_equal(np.asarray(ops.gf_matmul(a, b)), gf.matmul_np(a, b))


def test_gf_matmul_linearity():
    """Kernel respects GF linearity: A(B1 ^ B2) = AB1 ^ AB2."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 256, (4, 6), dtype=np.uint8)
    b1 = rng.integers(0, 256, (6, 100), dtype=np.uint8)
    b2 = rng.integers(0, 256, (6, 100), dtype=np.uint8)
    lhs = np.asarray(ops.gf_matmul(a, b1 ^ b2))
    rhs = np.asarray(ops.gf_matmul(a, b1)) ^ np.asarray(ops.gf_matmul(a, b2))
    np.testing.assert_array_equal(lhs, rhs)


@pytest.mark.parametrize("leaves,words", [(1, 4), (7, 256), (300, 256), (1000, 16), (257, 64)])
def test_sample_hash_shapes(leaves, words):
    rng = np.random.default_rng(leaves * 7 + words)
    w = rng.integers(0, 2**32, (leaves, words), dtype=np.uint32)
    out = np.asarray(ops.sample_hash(jnp.asarray(w)))
    ref = np.asarray(ops.sample_hash_ref(jnp.asarray(w)))
    np.testing.assert_array_equal(out, ref)


def test_sample_hash_seed_sensitivity():
    w = np.zeros((10, 8), np.uint32)
    h0 = np.asarray(ops.sample_hash(jnp.asarray(w), seed=0))
    h1 = np.asarray(ops.sample_hash(jnp.asarray(w), seed=1))
    assert not np.array_equal(h0, h1)


def test_sample_hash_avalanche():
    """Flipping one input bit changes the digest (for every tested leaf)."""
    rng = np.random.default_rng(3)
    w = rng.integers(0, 2**32, (64, 32), dtype=np.uint32)
    base = np.asarray(ops.sample_hash(jnp.asarray(w)))
    w2 = w.copy()
    w2[:, 17] ^= 1
    flipped = np.asarray(ops.sample_hash(jnp.asarray(w2)))
    assert (base != flipped).all()


def test_kernel_backs_the_rs_data_path():
    """RS encode via the Pallas kernel == numpy GF path (integration)."""
    from repro.core.rs import MDSCode

    rng = np.random.default_rng(11)
    code = MDSCode(n=9, k=6)
    data = rng.integers(0, 256, (6, 5000), dtype=np.uint8)
    cw_np = code.encode(data)
    cw_kern = code.encode(data, matmul=ops.gf_matmul_np)
    np.testing.assert_array_equal(cw_np, cw_kern)


@pytest.mark.parametrize("b,sq,sk,h,hkv,hd,causal,blk", [
    (1, 64, 64, 2, 2, 16, True, 32),
    (2, 128, 128, 4, 2, 32, True, 64),
    (1, 96, 96, 3, 1, 8, False, 32),
    (2, 64, 64, 8, 8, 64, True, 16),
])
def test_flash_attention_kernel_vs_ref(b, sq, sk, h, hkv, hd, causal, blk):
    import jax

    rng = np.random.default_rng(b * 100 + sq + h)
    q = jnp.asarray(rng.normal(size=(b, sq, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, sk, hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, sk, hkv, hd)).astype(np.float32))
    out = ops.flash_attention(q, k, v, causal=causal, bq=blk, bk=blk)
    ref = ops.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=1e-2)


def test_flash_attention_kernel_dtype_sweep():
    rng = np.random.default_rng(0)
    for dt in (jnp.float32, jnp.bfloat16):
        q = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32), dt)
        k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32), dt)
        v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32), dt)
        out = ops.flash_attention(q, k, v, bq=32, bk=32)
        ref = ops.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=5e-2)
