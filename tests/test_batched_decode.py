"""Batched Clay decode: byte-identical to the per-chunkset path (§3.5)."""
import numpy as np
import pytest

from repro.core.clay import ClayCode


def _codeword_sets(code, rng, trials, w=16):
    sets, refs = [], []
    for _ in range(trials):
        data = rng.integers(0, 256, (code.k, code.alpha, w), dtype=np.uint8)
        cw = code.encode(data)
        drop = rng.choice(code.n, size=int(rng.integers(0, code.m + 1)), replace=False)
        shards = {i: cw[i] for i in range(code.n) if i not in drop}
        sets.append(shards)
        refs.append(code.decode(shards))
    return sets, refs


def test_decode_batch_matches_per_chunkset(rng):
    code = ClayCode(k=4, m=2)
    sets, refs = _codeword_sets(code, rng, trials=8)
    for ref, got in zip(refs, code.decode_batch(sets)):
        assert np.array_equal(ref, got)


def test_decode_batch_mixed_erasure_patterns_grouped(rng):
    """Distinct erasure patterns land in distinct stacked solves."""
    code = ClayCode(k=3, m=3)
    sets, refs = _codeword_sets(code, rng, trials=10, w=8)
    patterns = {frozenset(s) for s in sets}
    assert len(patterns) > 1  # the grouping is actually exercised
    for ref, got in zip(refs, code.decode_batch(sets)):
        assert np.array_equal(ref, got)


def test_decode_batch_through_pallas_kernel(rng):
    from repro.kernels import ops

    code = ClayCode(k=4, m=2)
    sets, refs = _codeword_sets(code, rng, trials=4, w=8)
    for ref, got in zip(refs, code.decode_batch(sets, matmul=ops.gf_matmul_np)):
        assert np.array_equal(ref, got)


def test_decode_batch_rejects_too_few_shards(rng):
    code = ClayCode(k=4, m=2)
    sets, _ = _codeword_sets(code, rng, trials=1)
    sets[0] = {k: v for k, v in list(sets[0].items())[: code.k - 1]}
    with pytest.raises(ValueError):
        code.decode_batch(sets)


def test_rpc_batched_path_byte_identical(cluster, rng):
    """Acceptance: a multi-pattern batched decode returns the put() input."""
    contract, sps, rpc, client = cluster
    data = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    meta = client.put(data)
    # inject failures so the batch spans multiple erasure patterns
    sps[meta.placement[(0, 0)]].crash()
    sps[meta.placement[(1, 1)]].behavior.corrupt = True

    rpc._cache.clear()
    assert rpc.read_blob(meta.blob_id) == data


def test_rpc_single_chunkset_decodes_through_node_matmul(cluster, rng):
    """A one-chunkset miss goes through the node's GF matmul like a batch."""
    from repro.core import gf

    contract, sps, rpc, client = cluster
    shapes = []

    def matmul(a, b):
        shapes.append(b.shape)
        return gf.matmul_np(a, b)

    rpc.decode_matmul = matmul
    data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
    meta = client.put(data)
    assert meta.num_chunksets == 1
    assert shapes  # the put's encode ran through it too
    shapes.clear()
    sps[meta.placement[(0, 0)]].crash()
    assert rpc.read_blob(meta.blob_id) == data
    assert shapes  # the solve ran through the node's matmul
    assert rpc.stats.chunksets_decoded == 1
    assert rpc.stats.chunksets_decoded_on_host == 0
