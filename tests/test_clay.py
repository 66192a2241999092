"""Clay code properties: systematic, MDS (any k of n), optimal repair."""
import dataclasses
import itertools
import random

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the optional dep
from hypothesis import given, settings, strategies as st

from repro.core import gf
from repro.core.clay import ClayCode
from repro.core.rs import MDSCode

PARAMS = [(2, 2), (4, 2), (3, 3), (4, 3), (6, 3), (10, 6)]


def _codeword(k, m, w=6, seed=0):
    code = ClayCode(k=k, m=m)
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (k, code.alpha, w), dtype=np.uint8)
    return code, data, code.encode(data)


@pytest.mark.parametrize("k,m", PARAMS)
def test_systematic(k, m):
    code, data, cw = _codeword(k, m)
    assert np.array_equal(cw[:k], data)


@pytest.mark.parametrize("k,m", [(2, 2), (4, 2), (3, 3)])
def test_mds_exhaustive(k, m):
    """EVERY k-subset of the n chunks reconstructs the data."""
    code, data, cw = _codeword(k, m)
    for subset in itertools.combinations(range(code.n), k):
        rec = code.reconstruct_data({i: cw[i] for i in subset})
        assert np.array_equal(rec, data), subset


@pytest.mark.parametrize("k,m", [(4, 3), (6, 3), (10, 6)])
def test_mds_sampled(k, m):
    code, data, cw = _codeword(k, m)
    r = random.Random(42)
    for _ in range(12):
        subset = r.sample(range(code.n), k)
        rec = code.reconstruct_data({i: cw[i] for i in subset})
        assert np.array_equal(rec, data), subset


@pytest.mark.parametrize("k,m", PARAMS)
def test_decode_with_extra_shards(k, m):
    code, data, cw = _codeword(k, m)
    full = code.decode({i: cw[i] for i in range(code.n)})
    assert np.array_equal(full, cw)


@pytest.mark.parametrize("k,m", PARAMS)
def test_repair_every_node(k, m):
    """Single-node repair from repair-plane sub-chunks only, for all nodes."""
    code, data, cw = _codeword(k, m)
    ids = None
    for failed in range(code.n):
        ids = code.repair_subchunk_ids(failed)
        assert len(ids) == code.alpha // code.q  # alpha/q sub-chunks per helper
        helpers = {i: cw[i][ids] for i in range(code.n) if i != failed}
        rep = code.repair(failed, helpers)
        assert np.array_equal(rep, cw[failed]), failed


@pytest.mark.parametrize("k,m", PARAMS)
def test_repair_bandwidth_optimal(k, m):
    """MSR: clay repair reads (n-1)/(k*q) of what RS reads; always less for q>1."""
    code = ClayCode(k=k, m=m)
    rs = MDSCode(n=code.n, k=k)
    chunk = code.alpha * 8
    clay_bw = code.repair_bandwidth_bytes(chunk)
    rs_bw = rs.repair_bandwidth_bytes(chunk)
    assert clay_bw == (code.n - 1) * chunk // code.q
    if code.q > 1:
        assert clay_bw < rs_bw


def test_paper_production_code_saving():
    """(10,6): 75% repair-bandwidth saving >= the paper's '60% less than RS'."""
    code = ClayCode(k=10, m=6)
    chunk = code.alpha * 16
    saving = 1 - code.repair_bandwidth_bytes(chunk) / MDSCode(n=16, k=10).repair_bandwidth_bytes(chunk)
    assert saving >= 0.60
    assert abs(saving - 0.75) < 1e-9


def test_replication_overhead_below_2x():
    assert ClayCode(k=10, m=6).n / 10 == 1.6 < 2.0  # Table 1 claim


@given(st.integers(2, 4), st.integers(2, 3), st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_roundtrip_random_params(k, m, seed):
    code, data, cw = _codeword(k, m, w=4, seed=seed)
    r = random.Random(seed)
    erased = set(r.sample(range(code.n), m))
    shards = {i: cw[i] for i in range(code.n) if i not in erased}
    assert np.array_equal(code.decode(shards), cw)


@pytest.mark.parametrize("k,m,backend", [(k, m, "numpy") for k, m in PARAMS]
                         + [(4, 2, "kernel")])
def test_encode_through_a_matmul_is_byte_equal(k, m, backend):
    """`encode` of a code bound to a matmul gives `encode(data)`'s bytes,
    with one call of the (m, N - m) coefficient matrix per IS group.
    "kernel" runs the Pallas kernel (interpret mode off-TPU); wide codes
    stay on numpy, whose interpret-mode kernel takes seconds to trace."""
    from repro.kernels import ops

    code, data, cw = _codeword(k, m, w=16)
    inner = ops.gf_matmul_np if backend == "kernel" else gf.matmul_np
    calls = []

    def matmul(a, b):
        calls.append((a.shape, b.shape))
        return inner(a, b)

    groups = code._plan(tuple(sorted(code.real_to_flat[k:]))).groups
    known = code.N - m
    np.testing.assert_array_equal(dataclasses.replace(code, matmul=matmul).encode(data), cw)
    assert len(calls) == len(groups)
    assert all(a == (m, known) and b[0] == known for a, b in calls), calls
    assert sum(b[1] for _, b in calls) == code.alpha * 16


def test_too_few_shards_raises():
    code, data, cw = _codeword(4, 2)
    with pytest.raises(ValueError):
        code.decode({0: cw[0], 1: cw[1], 2: cw[2]})


def test_repair_needs_all_helpers():
    code, data, cw = _codeword(4, 2)
    ids = code.repair_subchunk_ids(0)
    helpers = {i: cw[i][ids] for i in range(1, code.n - 1)}  # one missing
    with pytest.raises(ValueError):
        code.repair(0, helpers)


# -- the plane schedule against the per-vertex loop it replaced ---------------
_THETA = int(gf.inv(np.uint8(1 ^ gf.mul(2, 2))))  # inv(1 + g^2), g = GAMMA = 2


def _reference_solve(code, c, unknown_flats):
    """The plane schedule vertex by vertex: a plain reading of Vajha et al.'s
    decoder (uncouple, solve, couple per IS group), independent of `_solve`."""
    g = 2
    c = c.copy()
    u = np.zeros_like(c)
    r_mat, known_used = code._decode_mats(tuple(sorted(unknown_flats)))
    groups = {}
    for z in code.planes:
        score = sum(code._flat(z[y], y) in unknown_flats for y in range(code.t))
        groups.setdefault(score, []).append(z)
    for score in sorted(groups):
        zis = [code.plane_index[z] for z in groups[score]]
        for z, zi in zip(groups[score], zis):
            for f in set(range(code.N)) - set(unknown_flats):
                p = code._partner(*code._xy(f), z)
                if p is None:
                    u[f, zi] = c[f, zi]
                else:
                    c_p = c[code._flat(p[0], p[1]), code.plane_index[p[2]]]
                    u[f, zi] = gf.mul(_THETA, c[f, zi] ^ gf.mul(g, c_p))
        kn = u[list(known_used)][:, zis].reshape(len(known_used), -1)
        rec = gf.matmul_np(r_mat, kn).reshape(len(unknown_flats), len(zis), -1)
        for row, f in enumerate(sorted(unknown_flats)):
            u[f, zis] = rec[row]
        for z, zi in zip(groups[score], zis):
            for f in unknown_flats:
                p = code._partner(*code._xy(f), z)
                if p is None:
                    c[f, zi] = u[f, zi]
                    continue
                pf, pzi = code._flat(p[0], p[1]), code.plane_index[p[2]]
                if pf in unknown_flats:
                    c[f, zi] = u[f, zi] ^ gf.mul(g, u[pf, pzi])
                else:
                    c[f, zi] = gf.mul(1 ^ gf.mul(g, g), u[f, zi]) ^ gf.mul(g, c[pf, pzi])
    return c


def _solve_cases():
    """(k, m, kind, erased real chunks, w): every erasure pattern of 1..m chunks
    at (4,2) and at (5,3) (one virtual node, q does not divide n), for
    `decode`; the parities for `encode`; two stacked chunksets for
    `decode_batch`.  w = 13 leaves a tail that packs into no wider word."""
    for k, m in [(4, 2), (5, 3)]:
        for w in (1, 8, 13):
            yield pytest.param(k, m, "encode", tuple(range(k, k + m)), w,
                               id=f"{k}-{m}-encode-w{w}")
            for e in range(1, m + 1):
                for erased in itertools.combinations(range(k + m), e):
                    kinds = ("decode", "batch") if w == 13 else ("decode",)
                    for kind in kinds:
                        yield pytest.param(k, m, kind, erased, w,
                                           id=f"{k}-{m}-{kind}-{'.'.join(map(str, erased))}-w{w}")


@pytest.mark.parametrize("k,m,kind,erased,w", list(_solve_cases()))
def test_solve_matches_the_per_vertex_reference(k, m, kind, erased, w):
    code = ClayCode(k=k, m=m)
    rng = np.random.default_rng(len(erased) * 131 + w)
    batch = 2 if kind == "batch" else 1
    cws = [code.encode(rng.integers(0, 256, (k, code.alpha, w), dtype=np.uint8))
           for _ in range(batch)]
    unknown = frozenset(code.real_to_flat[r] for r in erased)
    # the codeword on extended flats; erased chunks hold bytes the solve must not read
    c = code._blank(w * batch)
    for b, cw in enumerate(cws):
        for r, f in enumerate(code.real_to_flat):
            c[f, :, b * w:(b + 1) * w] = cw[r]
    for f in unknown:
        c[f] = 0 if kind == "encode" else rng.integers(0, 256, (code.alpha, w * batch),
                                                       dtype=np.uint8)
    ref = _reference_solve(code, c, unknown)
    ref_chunks = [ref[list(code.real_to_flat), :, b * w:(b + 1) * w] for b in range(batch)]
    for b, cw in enumerate(cws):
        np.testing.assert_array_equal(ref_chunks[b], cw)
    if kind == "batch":
        shards = [{r: cw[r] for r in range(code.n) if r not in erased} for cw in cws]
        for got, want in zip(code.decode_batch(shards), ref_chunks):
            np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_array_equal(code._solve(c, unknown), ref)


def test_the_plan_is_built_once_per_erasure_pattern():
    code, data, cw = _codeword(4, 2)
    ClayCode._plan.cache_clear()
    for _ in range(3):
        assert np.array_equal(code.decode({i: cw[i] for i in range(code.n) if i != 1}), cw)
    info = ClayCode._plan.cache_info()
    assert (info.misses, info.hits) == (1, 2)
    # its IS groups cover every plane once
    planes = np.concatenate([g.planes for g in code._plan((1,)).groups])
    assert sorted(planes) == list(range(code.alpha))
    assert np.array_equal(code.decode({i: cw[i] for i in range(code.n) if i not in (0, 5)}), cw)
    assert ClayCode._plan.cache_info().misses == 2
