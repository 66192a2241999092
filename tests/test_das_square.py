"""The DAS square against its plain reference (``core/das_reference.py``), the
bit-matrix GF kernel in interpret mode, the [256, 128] code, and the shape
rule by which ``kernels/ops.py`` chooses a kernel."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import das_reference as ref
from repro.core import gf
from repro.core.extend2d import Extend2D, commit_square
from repro.core.rs import MDSCode
from repro.kernels import gf_bitmatmul as _gb
from repro.kernels import ops


def _square(k, share_bytes, seed):
    return np.random.default_rng(seed).integers(0, 256, (k, k, share_bytes), dtype=np.uint8)


# -- the program's extension, commitments and proofs against the reference -----------
@pytest.mark.parametrize("k", [4, 8, 16])
def test_extension_and_commitments_match_the_reference(k):
    square = _square(k, 48, seed=k)
    ext = Extend2D(k).extend(square)
    np.testing.assert_array_equal(ext, ref.extend(square))
    rows, cols, das_root = ref.square_roots(ext)
    commitment = commit_square(ext).commitment
    assert list(commitment.row_roots) == rows
    assert list(commitment.col_roots) == cols
    assert commitment.das_root == das_root


@pytest.mark.parametrize("k", [4, 8, 16])
def test_share_proofs_lead_to_the_reference_roots(k):
    ext = Extend2D(k).extend(_square(k, 32, seed=100 + k))
    rows, cols, das_root = ref.square_roots(ext)
    csq = commit_square(ext)
    side = 2 * k
    coords = np.random.default_rng(k).integers(0, side, (12, 2))
    for (r, c), axis in zip(coords.tolist(), itertools.cycle(("row", "col"))):
        proof = csq.prove(r, c, axis=axis)
        share = ext[r, c].tobytes()
        axis_root = rows[r] if axis == "row" else cols[c]
        assert ref.path_root(share, proof.leaf_path.index, proof.leaf_path.path) == axis_root
        assert ref.path_root(axis_root, proof.root_path.index, proof.root_path.path) == das_root


def test_the_kernel_extends_a_square_as_the_reference_does():
    square = _square(16, 64, seed=9)  # (16, 16): the bit-matrix kernel
    assert ops.uses_bit_matrix(16, 16)
    np.testing.assert_array_equal(Extend2D(16).extend(square, matmul=ops.gf_matmul_np),
                                  ref.extend(square))


def test_the_reference_is_the_same_file_in_the_program_and_the_benchmark():
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    assert (root / "bench/das_reference.py").read_bytes() == \
        (root / "src/repro/core/das_reference.py").read_bytes()


def test_the_reference_field_and_inverse():
    a = np.arange(256, dtype=np.uint8)
    np.testing.assert_array_equal(ref.MUL, gf.mul(a[:, None], a[None, :]))
    m = np.random.default_rng(4).integers(0, 256, (6, 6), dtype=np.uint8)
    np.testing.assert_array_equal(ref.inverse(m), gf.mat_inv(m))


# -- the [256, 128] code ---------------------------------------------------------------------
def test_field_points_end_with_zero_at_length_256():
    pts = gf.field_points(256)
    assert pts.tolist() == list(range(1, 256)) + [0]
    assert gf.field_points(10).tolist() == list(range(1, 11))
    np.testing.assert_array_equal(ref.points(256), pts)
    with pytest.raises(ValueError):
        gf.field_points(257)
    np.testing.assert_array_equal(MDSCode(256, 128).parity_check, ref.parity_check(256, 128))


def test_code_256_128_encodes_as_the_reference():
    data = np.random.default_rng(1).integers(0, 256, (128, 40), dtype=np.uint8)
    np.testing.assert_array_equal(MDSCode(256, 128).encode(data), ref.encode(data, 256))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_code_256_128_is_mds_on_random_subsets(seed):
    code = MDSCode(256, 128)
    rng = np.random.default_rng(seed)
    cw = code.encode(rng.integers(0, 256, (128, 8), dtype=np.uint8))
    known = sorted(rng.choice(256, 128, replace=False).tolist())
    if seed == 0:
        known = list(range(128, 256))  # only parity, the point 0 among them
    out = code.decode({i: cw[i] for i in known})
    np.testing.assert_array_equal(out, cw)


# -- the bit-matrix kernel in interpret mode --------------------------------------------------
@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (3, 5, 700), (8, 8, 512), (16, 16, 1000), (17, 9, 1025), (128, 128, 64),
])
def test_gf_bitmatmul_matches_numpy(m, k, n):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    a = rng.integers(0, 256, (m, k), dtype=np.uint8)
    b = rng.integers(0, 256, (k, n), dtype=np.uint8)
    out = np.asarray(_gb.gf_bitmatmul(a, b, interpret=True))
    np.testing.assert_array_equal(out, gf.matmul_np(a, b))


@pytest.mark.parametrize("block_n", [128, 1024])
def test_gf_bitmatmul_block_sizes(block_n):
    rng = np.random.default_rng(block_n)
    a = rng.integers(0, 256, (20, 24), dtype=np.uint8)
    b = rng.integers(0, 256, (24, 3000), dtype=np.uint8)
    out = np.asarray(_gb.gf_bitmatmul(a, b, block_n=block_n, interpret=True))
    np.testing.assert_array_equal(out, gf.matmul_np(a, b))


def test_bit_matrix_is_multiplication_by_each_coefficient():
    a = np.array([[0x00, 0x01, 0x02], [0x53, 0xCA, 0xFF]], np.uint8)
    bits = np.asarray(_gb.bit_matrix(jnp.asarray(a)))
    m, k = a.shape
    for i, j, q in itertools.product(range(m), range(k), range(8)):
        product = int(gf.mul(a[i, j], 1 << q))
        column = bits[[p * m + i for p in range(8)], q * k + j]
        assert sum(int(bit) << p for p, bit in enumerate(column)) == product


def test_gf_bitmatmul_lowers_to_a_module_the_gf_matmul_roofline_cannot_match():
    """``gf_matmul_roofline`` sums programs whose name contains ``gf_matmul``."""
    a, b = jnp.zeros((16, 16), jnp.uint8), jnp.zeros((16, 512), jnp.uint8)
    text = _gb.gf_bitmatmul.lower(a, b, interpret=True).as_text()
    assert text.startswith("module @jit_gf_bitmatmul ")


# -- the shape rule --------------------------------------------------------------------------
def _decode_shapes(k, m):
    """(M, K) of every kernel call of Clay decodes at a layout, over erasure
    patterns of every size (w = 1: the shapes do not depend on w)."""
    from repro.core.clay import ClayCode

    code = ClayCode(k=k, m=m)
    cw = code.encode(np.random.default_rng(k).integers(0, 256, (k, code.alpha, 1),
                                                        dtype=np.uint8))
    shapes = set()

    def record(a, b):
        shapes.add(a.shape)
        return gf.matmul_np(a, b)

    n = k + m
    for e in range(1, m + 1):
        for erased in (range(e), range(n - e, n), range(0, 2 * e, 2)):
            shards = {i: cw[i] for i in range(n) if i not in set(erased)}
            np.testing.assert_array_equal(code.decode_batch([shards], matmul=record)[0], cw)
    return shapes


@pytest.mark.parametrize("k,m", [(10, 6), (16, 4)])
def test_every_benchmark_clay_shape_stays_on_the_vpu_kernel(k, m):
    shapes = _decode_shapes(k, m)
    assert shapes and max(mm for mm, _ in shapes) <= m and max(kk for _, kk in shapes) <= 19
    assert not any(ops.uses_bit_matrix(*shape) for shape in shapes), shapes


def test_the_das_squares_choose_their_kernel_by_shape():
    assert not ops.uses_bit_matrix(4, 4)  # shelby-10-6.put-stream's (4, 4) square
    assert not ops.uses_bit_matrix(6, 19)
    assert ops.uses_bit_matrix(128, 128)  # Celestia's 128 x 128 square
    assert ops.uses_bit_matrix(16, 16)


def test_gf_matmul_dispatches_by_shape(monkeypatch):
    called = []
    for mod, name in ((_gb, "gf_bitmatmul"), (ops._gf, "gf_matmul")):
        real = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda a, b, _n=name, _r=real, **kw:
                            called.append(_n) or _r(a, b, **kw))
    rng = np.random.default_rng(0)
    for m, k in ((4, 4), (16, 16)):
        a = rng.integers(0, 256, (m, k), dtype=np.uint8)
        b = rng.integers(0, 256, (k, 100), dtype=np.uint8)
        np.testing.assert_array_equal(ops.gf_matmul_np(a, b), gf.matmul_np(a, b))
    assert called == ["gf_matmul", "gf_bitmatmul"]


# -- the put path at a DAS square: counters --------------------------------------------------
def test_a_put_counts_the_square_it_extends_and_the_shares_it_places():
    from repro.launch.train import build_cluster
    from repro.storage.das import DASSpec

    _, sps, _, client = build_cluster(num_sps=8)
    client.das = DASSpec(k=8, share_bytes=64)
    data = np.random.default_rng(5).integers(0, 256, 8 * 8 * 64, dtype=np.uint8).tobytes()
    meta = client.put(data)
    assert client.stats.das_squares_extended == 1
    assert client.stats.das_shares_placed == 16 * 16
    assert sum(sp.stored_shares() for sp in sps.values()) == 16 * 16
    rec = client.contract.das[meta.blob_id]
    assert rec.das_root == ref.square_roots(ref.extend(
        np.frombuffer(data, np.uint8).reshape(8, 8, 64)))[2]
