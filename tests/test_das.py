"""Data-availability sampling (ISSUE 7): the 2-D extension, proof-carrying
tiny reads, and the light-client sampling plane.

Covers the tentpole — the k x k -> 2k x 2k extension (any k rows/columns
reconstruct the square bit-exact), coordinate-bound share proofs, the
sampler's measured detection rate against the analytic ``1-(1-q)^s`` over
multiple seeds AND withholding fractions — plus the satellites: pay-per-
sample receipts under settlement conservation, the ``cache_bypass``
steering hint, storm determinism, the batched small-and-wide GF path
(numpy == Pallas), and the config plumbing into ``run_sim``.
"""
import numpy as np
import pytest

from repro.configs.shelby import ShelbyConfig
from repro.core import extend2d
from repro.core.extend2d import Extend2D, commit_square, detection_probability
from repro.core.simulation import honest_population, run_sim
from repro.kernels import ops
from repro.net.workloads import das_storm
from repro.storage import das
from repro.storage.das import (
    DASSpec,
    LightClientSampler,
    measure_detection,
    seed_withholding,
)

SPEC = DASSpec(k=4, share_bytes=64, samples_per_epoch=16)


def _square(k=4, share_bytes=64, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (k, k, share_bytes), dtype=np.uint8)


# -- the 2-D extension --------------------------------------------------------
def test_extension_is_systematic():
    lay = Extend2D(k=4)
    sq = _square()
    ext = lay.extend(sq)
    assert ext.shape == (8, 8, 64)
    assert np.array_equal(ext[:4, :4], sq)  # data survives in the corner


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_any_k_rows_reconstruct_bit_exact(seed):
    lay = Extend2D(k=4)
    ext = lay.extend(_square(seed=seed))
    rng = np.random.default_rng(seed + 100)
    for _ in range(4):
        rows = sorted(rng.choice(lay.side, size=lay.k, replace=False))
        got = lay.reconstruct_from_rows(
            {int(r): np.ascontiguousarray(ext[r]) for r in rows}
        )
        assert np.array_equal(got, ext)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_any_k_cols_reconstruct_bit_exact(seed):
    lay = Extend2D(k=4)
    ext = lay.extend(_square(seed=seed))
    rng = np.random.default_rng(seed + 200)
    for _ in range(4):
        cols = sorted(rng.choice(lay.side, size=lay.k, replace=False))
        got = lay.reconstruct_from_cols(
            {int(c): np.ascontiguousarray(ext[:, c]) for c in cols}
        )
        assert np.array_equal(got, ext)


def test_extend_batch_matches_single_and_pallas():
    lay = Extend2D(k=4)
    squares = [_square(seed=s) for s in range(5)]
    batched = lay.extend_batch(squares)
    for sq, ext in zip(squares, batched):
        assert np.array_equal(ext, lay.extend(sq))
    # the Pallas GF matmul (interpret mode off-TPU) is byte-identical
    pallas = lay.extend_batch(squares, matmul=ops.gf_matmul_np)
    for a, b in zip(batched, pallas):
        assert np.array_equal(a, b)


# -- proof-carrying shares ----------------------------------------------------
def test_share_proofs_verify_on_both_axes():
    lay = Extend2D(k=4)
    csq = commit_square(lay.extend(_square()))
    root = csq.commitment.das_root
    for axis in ("row", "col"):
        proof = csq.prove(2, 5, axis=axis)
        assert proof.nbytes > 0
        assert extend2d.verify_share(root, lay.side, csq.share(2, 5).tobytes(),
                                     proof)


def test_proof_rejects_tamper_and_replay():
    lay = Extend2D(k=4)
    csq = commit_square(lay.extend(_square()))
    root = csq.commitment.das_root
    proof = csq.prove(2, 5, axis="row")
    # tampered share bytes
    bad = bytearray(csq.share(2, 5).tobytes())
    bad[0] ^= 0xFF
    assert not extend2d.verify_share(root, lay.side, bytes(bad), proof)
    # a valid proof replayed at another coordinate (coordinate binding)
    forged = extend2d.ShareProof(row=3, col=5, axis="row",
                                 axis_root=proof.axis_root,
                                 leaf_path=proof.leaf_path,
                                 root_path=proof.root_path)
    assert not extend2d.verify_share(root, lay.side,
                                     csq.share(2, 5).tobytes(), forged)
    # wrong root
    assert not extend2d.verify_share(b"\x00" * 32, lay.side,
                                     csq.share(2, 5).tobytes(), proof)


# -- placement and proofs as arrays --------------------------------------------
SQUARE_KS = [2, 3, 4, 16]  # 3: a side of 6, whose trees pad to 8 leaves


def _dispersed(k, *, crash=None, seed=0):
    """One blob's square placed on 6 SPs; ``crash`` goes down first."""
    spec = DASSpec(k=k, share_bytes=64)
    contract, sps, _, (blob_id,) = das._mini_world(
        6, DASSpec(k=k, share_bytes=64, extension=False), 1, seed)
    if crash is not None:
        sps[crash].crash()
    square = _square(k=k, seed=seed)
    rec = das.extend_and_disperse(contract, sps, blob_id, square.tobytes(), spec)
    return contract, sps, rec, commit_square(Extend2D(k=k).extend(square))


def _coords(side):
    return [(r, c) for r in range(side) for c in range(side)]


@pytest.mark.parametrize("k", SQUARE_KS)
def test_array_draw_places_like_one_scalar_draw_a_share(k):
    contract, sps, rec, _ = _dispersed(k)
    active = [info.sp_id for info in contract.active_sps()]
    rng = das.placement_mod._rng(contract.epoch_seed(contract.epoch), b"das", rec.blob_id)
    expected = {(r, c): active[int(rng.integers(0, len(active)))]
                for r, c in _coords(rec.side)}
    assert dict(rec.placement) == expected
    assert sum(sp.stored_shares() for sp in sps.values()) == rec.side ** 2


@pytest.mark.parametrize("k", SQUARE_KS)
def test_every_share_is_served_with_the_proof_the_square_gives(k):
    _, sps, rec, csq = _dispersed(k)
    assert rec.das_root == csq.commitment.das_root
    assert rec.proof_bytes == csq.prove(0, 0, axis="row").nbytes
    for r, c in _coords(rec.side):
        share, proof, _ = sps[rec.placement[(r, c)]].serve_share(rec.blob_id, r, c)
        assert share.tobytes() == csq.share(r, c).tobytes()
        assert proof == csq.prove(r, c, axis="row" if (r + c) % 2 == 0 else "col")
        assert extend2d.verify_share(rec.das_root, rec.side, share.tobytes(), proof)


@pytest.mark.parametrize("k", SQUARE_KS)
def test_an_sp_crashed_at_put_time_holds_none_of_its_shares(k):
    _, sps, rec, _ = _dispersed(k, crash=1)
    sps[1].recover()
    assert sps[1].stored_shares() == 0
    for r, c in _coords(rec.side):
        got = sps[rec.placement[(r, c)]].serve_share(rec.blob_id, r, c)
        assert (got is None) == (rec.placement[(r, c)] == 1)
    held = sum(sp_id != 1 for sp_id in rec.placement.values())
    assert sum(sp.stored_shares() for sp in sps.values()) == held


@pytest.mark.parametrize("k", SQUARE_KS)
def test_withholding_and_wipe_silence_shares(k):
    contract, sps, rec, _ = _dispersed(k)

    def silent():
        return {(r, c) for r, c in _coords(rec.side)
                if sps[rec.placement[(r, c)]].serve_share(rec.blob_id, r, c) is None}

    w = seed_withholding(contract, sps, rec.blob_id, 0.25, seed=1)
    assert w == round(0.25 * rec.side ** 2) and len(silent()) == w
    withheld = silent()
    sps[2].wipe()
    assert sps[2].stored_shares() == 0
    assert silent() == withheld | {rc for rc, sp_id in rec.placement.items() if sp_id == 2}
    for sp in sps.values():  # out of the square: nothing aliases another share
        assert sp.serve_share(rec.blob_id, 0, rec.side) is None
        assert sp.serve_share(rec.blob_id, -1, 0) is None


@pytest.mark.parametrize("k", SQUARE_KS)
def test_placement_reads_as_the_dict_did(k):
    contract, _, rec, _ = _dispersed(k)
    placement = rec.placement
    side = rec.side
    assert len(placement) == side * side
    assert list(placement) == _coords(side)  # row-major, the dict's insertion order
    assert list(placement.items()) == [(rc, placement[rc]) for rc in _coords(side)]
    assert all(isinstance(sp_id, int) for sp_id in placement.values())
    assert set(placement.values()) <= {info.sp_id for info in contract.active_sps()}
    assert (side - 1, side - 1) in placement
    for missing in [(side, 0), (0, side), (-1, 0), (0,), "x"]:
        assert missing not in placement
        with pytest.raises(KeyError):
            placement[missing]
    with pytest.raises(TypeError):
        placement[(0, 0)] = 1
    with pytest.raises(ValueError):
        placement.sp_ids[0, 0] = 1


# -- detection: measured vs analytic -----------------------------------------
def test_detection_matches_analytic_across_seeds_and_fractions():
    # >= 3 fractions x >= 3 seeds; exact-count withholding + with-
    # replacement draws make 1-(1-q)^s exact, so tolerance is pure
    # Monte-Carlo noise on 64 Bernoulli trials per cell (~3 sigma)
    points = measure_detection(
        fractions=(0.05, 0.15, 0.30), seeds=(0, 1, 2),
        spec=SPEC, num_blobs=8, rounds=8,
    )
    assert len(points) == 9
    for pt in points:
        assert pt.analytic == detection_probability(pt.q_effective, pt.samples)
        assert abs(pt.measured - pt.analytic) <= 0.2, (
            f"q={pt.q_effective:.3f}: measured {pt.measured:.3f} "
            f"vs analytic {pt.analytic:.3f}"
        )


def test_zero_withholding_never_detects():
    points = measure_detection(fractions=(0.0,), seeds=(0,), spec=SPEC,
                               num_blobs=4, rounds=4)
    (pt,) = points
    assert pt.q_effective == 0.0 and pt.analytic == 0.0
    assert pt.detected == 0, "false positive with nothing withheld"


def test_detection_cheaper_than_full_chunk_audit():
    # a withholding SP RETAINS the data, so possession audits never fire;
    # the sampler catches it for less than one full-chunk audit read
    points = measure_detection(fractions=(0.30,), seeds=(0,), spec=SPEC,
                               num_blobs=6, rounds=6)
    (pt,) = points
    assert pt.detected > 0
    chunk_bytes = 64 * 1024 // 4  # the mini-world layout's full chunk
    assert pt.mean_samples_to_detect * pt.mean_sample_bytes < chunk_bytes


# -- the serving path: pay-per-sample, steering, receipts ---------------------
def test_sample_availability_pays_and_conserves():
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 2, seed=0)
    session = client.current_session
    before = len(session.receipts)
    verdicts = session.sample_availability(blob_ids, epoch=0, samples=8, seed=1)
    assert len(verdicts) == 2
    for v in verdicts:
        assert v.available and v.failures == 0
        assert v.verified == 8 and v.samples == 8
        assert v.sample_bytes > 0 and v.proof_bytes > 0
        assert v.paid > 0.0
    recs = session.receipts[before:]
    assert len(recs) == 16 and all(r.verified for r in recs)
    rec = contract.das[blob_ids[0]]
    assert all(r.nbytes == SPEC.share_bytes + rec.proof_bytes for r in recs)
    client.settle()  # conservation asserted inside close()


def test_withheld_samples_detect_and_debit_nothing():
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 1, seed=0)
    w = seed_withholding(contract, sps, blob_ids[0], 1.0)
    assert w == SPEC.side * SPEC.side
    session = client.current_session
    (v,) = session.sample_availability(blob_ids, epoch=0, samples=4, seed=2)
    assert not v.available and v.failures == 4 and v.verified == 0
    assert v.first_failure == 0
    assert v.paid == 0.0 and v.sample_bytes == 0
    client.settle()


def test_cache_bypass_steers_the_hot_cache():
    # default (bypass): repeated sampling of the same epoch re-fetches and
    # re-pays — nothing of the storm lands in the hot cache
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 1, seed=0)
    session = client.current_session
    node = client.fleet.primary
    session.sample_availability(blob_ids, epoch=0, samples=6, seed=3)
    session.sample_availability(blob_ids, epoch=0, samples=6, seed=3)
    assert node.stats.das_cache_hits == 0
    # counterfactual: the hint off -> the identical second round is served
    # from cache (free, proof already verified)
    contract2, sps2, client2, blob_ids2 = das._mini_world(6, SPEC, 1, seed=0)
    session2 = client2.current_session
    node2 = client2.fleet.primary
    session2.sample_availability(blob_ids2, epoch=0, samples=6, seed=3,
                                 cache_bypass=False)
    session2.sample_availability(blob_ids2, epoch=0, samples=6, seed=3,
                                 cache_bypass=False)
    assert node2.stats.das_cache_hits > 0
    cached = [r for r in session2.receipts if getattr(r, "cache_hit", False)]
    assert cached and all(r.proof_bytes == 0 for r in cached)
    client.settle()
    client2.settle()


def test_light_client_sampler_detections():
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 2, seed=0)
    seed_withholding(contract, sps, blob_ids[1], 0.5)
    sampler = LightClientSampler(client.current_session, SPEC, seed=0)
    verdicts = sampler.sample_epoch(0, blob_ids)
    assert len(verdicts) == 2
    by_blob = {v.blob_id: v for v in verdicts}
    assert by_blob[blob_ids[0]].available
    # q=0.5, s=16: detection probability 1 - 2^-16 — this must fire
    assert not by_blob[blob_ids[1]].available
    assert sampler.detections == 1
    client.settle()


# -- determinism --------------------------------------------------------------
def test_das_storm_is_a_pure_function_of_its_seed():
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 2, seed=0)
    recs = [contract.das[b] for b in blob_ids]
    a = das_storm(recs, clients=["c0", "c1"], num_requests=40, seed=9)
    b = das_storm(recs, clients=["c0", "c1"], num_requests=40, seed=9)
    assert a == b
    c = das_storm(recs, clients=["c0", "c1"], num_requests=40, seed=10)
    assert a != c
    assert all(0 <= r.row < SPEC.side and 0 <= r.col < SPEC.side for r in a)
    assert all(r.cache_bypass for r in a)


def test_draw_coords_deterministic_and_in_range():
    a = das.draw_coords(5, blob_id=1, epoch=3, s=32, side=8)
    b = das.draw_coords(5, blob_id=1, epoch=3, s=32, side=8)
    assert a == b and len(a) == 32
    assert das.draw_coords(5, blob_id=1, epoch=4, s=32, side=8) != a
    assert all(0 <= r < 8 and 0 <= c < 8 for r, c in a)


def test_session_replay_counts_das_records():
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 2, seed=0)
    recs = [contract.das[b] for b in blob_ids]
    reqs = das_storm(recs, clients=["c0"], num_requests=30, seed=4)

    def one():
        c = das._mini_world(6, SPEC, 2, seed=0)[2]
        with c.session() as session:
            _, result = session.replay(reqs)
        return result

    ra, rb = one(), one()
    assert ra.das_samples == 30 and ra.das_detections == 0
    assert ra.digest() == rb.digest()


# -- config + simulation plumbing --------------------------------------------
def test_config_das_spec_roundtrip():
    cfg = ShelbyConfig(das_k=2, das_share_bytes=128, das_samples_per_epoch=4,
                       das_proof_bytes_per_share=99)
    spec = cfg.das()
    assert spec == DASSpec(k=2, share_bytes=128, samples_per_epoch=4,
                           proof_bytes_per_share=99)
    assert ShelbyConfig(das_extension=False).das() is None


def test_proof_bytes_override_lands_on_the_record():
    spec = DASSpec(k=2, share_bytes=32, proof_bytes_per_share=1234)
    contract, sps, client, blob_ids = das._mini_world(6, spec, 1, seed=0)
    assert contract.das[blob_ids[0]].proof_bytes == 1234


def test_put_disperses_shares_when_das_enabled():
    contract, sps, client, blob_ids = das._mini_world(6, SPEC, 1, seed=0)
    rec = contract.das[blob_ids[0]]
    assert rec.side == SPEC.side
    assert set(rec.placement) == {
        (r, c) for r in range(rec.side) for c in range(rec.side)
    }
    stored = sum(sp.stored_shares() for sp in sps.values())
    assert stored == rec.side * rec.side


def test_run_sim_with_das_plane():
    spec = DASSpec(k=2, share_bytes=64, samples_per_epoch=4)
    res = run_sim(honest_population(6), epochs=2, num_blobs=2,
                  blob_bytes=2 * 2 * 64, das=spec, seed=1)
    assert res.das_samples == 2 * 2 * 4  # epochs x blobs x samples
    assert res.das_detections == 0
    assert res.das_proof_bytes > 0
    # the switch off: no dispersal, no sampling
    res_off = run_sim(honest_population(6), epochs=1, num_blobs=2,
                      blob_bytes=2 * 2 * 64, das=None, seed=1)
    assert res_off.das_samples == 0 and res_off.das_proof_bytes == 0
