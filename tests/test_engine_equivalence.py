"""Engine equivalence: the calendar queue must be *invisible* to every
determinism digest, and replayed sessions must settle deterministically.

Two families of bars:

* ``CalendarQueue`` vs ``_BinaryHeap`` pop-order identity — a seeded
  hand-rolled property sweep (hypothesis is not in the image) over random
  push/pop interleavings with exact-time ties, far-future timestamps, and
  zero-delay self-wakes, plus digest equality of full replays across the
  workload families (zipf streaming, DAS storm, membership churn,
  background planes) with ``engine="heap"`` vs ``engine="calendar"``.
* Replay settlement — a paid session's open-loop replay digests equal on
  fresh worlds, and every node's settled income equals what its receipts
  say was paid.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.contract import ShelbyContract
from repro.core.placement import SPInfo
from repro.net.backbone import Backbone
from repro.net.events import CalendarQueue, EventLoop, _BinaryHeap
from repro.net.fleet import CacheAffinityPolicy, RPCFleet
from repro.net.workloads import (
    das_storm,
    replay_open_loop,
    zipf_hotset,
)
from repro.core import audit as audit_mod
from repro.storage.background import AuditPlane, RepairPlane
from repro.storage.blob import BlobLayout
from repro.storage.das import DASSpec, extend_and_disperse_many
from repro.storage.membership import ChurnSpec, MembershipPlane
from repro.storage.repair import RepairCoordinator
from repro.storage.rpc import BackboneTransport, RPCNode
from repro.storage.sdk import ShelbyClient
from repro.storage.sp import BackgroundSpec, ServiceSpec, StorageProvider


# ---------------------------------------------------------------------------
# calendar queue vs binary heap: pop-order identity (property sweep)
# ---------------------------------------------------------------------------
def _drain_equal(items, *, width_ms=1.0):
    """Push the same items into both disciplines, pop everything, and
    assert the sequences are identical element-for-element."""
    cal, heap = CalendarQueue(width_ms=width_ms), _BinaryHeap()
    for it in items:
        cal.push(it)
        heap.push(it)
    assert len(cal) == len(heap) == len(items)
    got = [cal.pop() for _ in range(len(items))]
    want = [heap.pop() for _ in range(len(items))]
    assert got == want
    assert len(cal) == 0
    with pytest.raises(IndexError):  # empty-pop contract matches heappop
        cal.pop()


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("width_ms", [0.25, 1.0, 16.0])
def test_calendar_pop_order_matches_heap_property_sweep(seed, width_ms):
    """Seeded stand-in for a hypothesis property test: random (t, seq)
    streams with heavy exact-time ties and day-boundary times."""
    rng = np.random.default_rng(seed)
    n = 400
    # a small palette of times forces many exact ties; day-boundary
    # multiples of width land items right on bucket edges
    palette = np.concatenate([
        rng.uniform(0.0, 50.0, 8),
        np.arange(6) * width_ms,           # exact day boundaries
        [0.0, 0.0, 13.125],                # repeated zeros: tie storms
    ])
    ts = rng.choice(palette, n)
    seqs = rng.permutation(n)  # unique seqs, shuffled vs time order
    items = [(float(t), int(s), f"task{s}") for t, s in zip(ts, seqs)]
    _drain_equal(items, width_ms=width_ms)


def test_calendar_interleaved_push_pop_matches_heap():
    """Pops interleave with pushes (as a live loop does): after each pop
    both disciplines must agree, including pushes at the just-popped time
    (zero-delay self-wakes land in the current day)."""
    rng = np.random.default_rng(42)
    cal, heap = CalendarQueue(), _BinaryHeap()
    seq = 0
    now = 0.0
    for _ in range(200):
        for _ in range(rng.integers(1, 4)):
            t = now + float(rng.exponential(2.0))
            if rng.random() < 0.3:
                t = now  # zero-delay self-wake: same time, later seq
            cal.push((t, seq, None))
            heap.push((t, seq, None))
            seq += 1
        if len(heap) and rng.random() < 0.8:
            a, b = cal.pop(), heap.pop()
            assert a == b
            now = a[0]
    while len(heap):
        assert cal.pop() == heap.pop()


def test_calendar_far_future_and_sparse_days():
    """Dict-keyed days: timestamps out at 1e12 ms (a classic modulo-ring
    year wrap hazard) order correctly against near-term events, and
    all-sparse streams (every event its own day) stay exact."""
    items = [(1e12, 1, "far"), (0.0, 0, "now"), (1e12, 0, "far-tie"),
             (5e11 + 0.5, 2, "mid"), (1e12 + 1e-9, 3, "epsilon-later")]
    _drain_equal(items)
    sparse = [(float(i) * 1e6, i, None) for i in range(64)][::-1]
    _drain_equal(sparse)


def test_calendar_rejects_nonpositive_width():
    with pytest.raises(ValueError):
        CalendarQueue(width_ms=0.0)


# ---------------------------------------------------------------------------
# heap vs calendar digests across the workload families
# ---------------------------------------------------------------------------
def _bb_world(*, num_sps=9, num_rpcs=2, cache=8, seed=0, num_blobs=4,
              blob_bytes=150_000, crash_sp=None, single_flight=True):
    layout = BlobLayout(k=4, m=2, chunkset_bytes_target=64 * 1024)
    contract = ShelbyContract()
    bb = Backbone.mesh(3, base_latency_ms=4.0, gbps=10.0)
    sps = {}
    for i in range(num_sps):
        dc = f"dc{i % 3}"
        contract.register_sp(SPInfo(sp_id=i, stake=1000.0, dc=dc, rack=f"r{i % 4}"))
        sps[i] = StorageProvider(i, service=ServiceSpec(
            disk_ms_per_chunk=1.0, slots=2, background=BackgroundSpec()))
        bb.register_node(f"sp{i}", dc)
    rpcs = []
    for r in range(num_rpcs):
        node = f"rpc{r}"
        bb.register_node(node, f"dc{r % 3}")
        rpcs.append(RPCNode(node, contract, sps, layout, cache_chunksets=cache,
                            transport=BackboneTransport(sps, bb, node),
                            single_flight=single_flight))
    bb.register_node("client", "dc0")
    bb.register_node("repairer", "dc1")
    fleet = RPCFleet(rpcs, CacheAffinityPolicy(), backbone=bb)
    client = ShelbyClient(contract, fleet, deposit=1e9)
    rng = np.random.default_rng(seed)
    metas = [client.put(rng.integers(0, 256, blob_bytes, dtype=np.uint8).tobytes())
             for _ in range(num_blobs)]
    if crash_sp is not None:
        sps[crash_sp].crash()  # after the writes: its chunks are repair work
    return layout, contract, bb, sps, fleet, client, metas


def _family_zipf(engine):
    *_, fleet, _, metas = _bb_world()
    reqs = zipf_hotset(metas, clients=["client"], num_requests=80,
                       interarrival_ms=2.0, seed=3, arrival="poisson")
    return replay_open_loop(fleet, reqs, engine=engine).digest()


def _family_das(engine):
    layout, contract, _, sps, fleet, client, metas = _bb_world()
    spec = DASSpec(k=4, share_bytes=512, samples_per_epoch=8)
    rng = np.random.default_rng(1)
    records = extend_and_disperse_many(
        contract, sps,
        [(m.blob_id, rng.integers(0, 256, 4096, dtype=np.uint8).tobytes())
         for m in metas[:2]],
        spec,
    )
    reqs = das_storm(records, clients=["client"], num_requests=60, seed=9,
                     interarrival_ms=1.0)
    reader = ShelbyClient(contract, fleet, deposit=1e9, das=spec)
    with reader.session() as session:
        _, result = session.replay(reqs, engine=engine)
    return result.digest()


def _family_churn(engine):
    layout, contract, _, sps, fleet, client, metas = _bb_world(num_sps=10)
    rc = RepairCoordinator(contract, sps, layout)
    plane = MembershipPlane(
        contract, sps, layout,
        ChurnSpec(p_crash=0.1, p_leave=0.1, joins_per_epoch=1, seed=4),
        repair=rc, fleet=fleet, epochs=2, epoch_ms=60.0,
    )
    reqs = zipf_hotset(metas, clients=["client"], num_requests=40,
                       interarrival_ms=3.0, seed=8, arrival="poisson")
    with client.session() as session:
        _, result = session.replay(reqs, background=plane.planes(),
                                   engine=engine)
    return result.digest()


def _family_background(engine):
    layout, contract, _, sps, fleet, _, metas = _bb_world(crash_sp=5)
    sp_nodes = {i: f"sp{i}" for i in sps}
    sp_ids = [s.sp_id for s in contract.active_sps()]
    challenges = audit_mod.derive_challenges(
        contract.epoch_seed(0), 0, contract.holdings(), sp_ids,
        p_a=1.0, auditors_per_audit=3,
    )
    audits = AuditPlane(contract, sps, challenges, nodes=sp_nodes)
    rc = RepairCoordinator(contract, sps, layout, nodes=sp_nodes,
                           coordinator_node="repairer")
    reqs = zipf_hotset(metas, clients=["client"], num_requests=50,
                       interarrival_ms=2.0, seed=3, arrival="poisson")
    return replay_open_loop(fleet, reqs,
                            background=[audits, RepairPlane(rc)],
                            engine=engine).digest()


@pytest.mark.parametrize("family", [
    _family_zipf, _family_das, _family_churn, _family_background,
], ids=["zipf_streaming", "das_storm", "membership_churn", "background_planes"])
def test_heap_and_calendar_digests_identical(family):
    assert family("heap") == family("calendar")


def test_default_engine_is_calendar():
    assert EventLoop().engine == "calendar"
    # an unknown discipline fails loudly, not silently-heap
    with pytest.raises(ValueError):
        EventLoop(engine="fibonacci")


# ---------------------------------------------------------------------------
# replay settlement: determinism and value conservation
# ---------------------------------------------------------------------------
def _replay_world(*, num_rpcs=2, cache=64):
    """Two cached RPC nodes over eight SPs, twelve single-chunkset blobs."""
    layout = BlobLayout(k=4, m=2, chunkset_bytes_target=64 * 1024)
    contract = ShelbyContract()
    bb = Backbone.mesh(3, base_latency_ms=4.0, gbps=10.0)
    sps = {}
    for i in range(8):
        dc = f"dc{i % 3}"
        contract.register_sp(SPInfo(sp_id=i, stake=1000.0, dc=dc, rack=f"r{i % 4}"))
        sps[i] = StorageProvider(i, service=ServiceSpec(disk_ms_per_chunk=0.5,
                                                        slots=4))
        bb.register_node(f"sp{i}", dc)
    rpcs = []
    for r in range(num_rpcs):
        node = f"rpc{r}"
        bb.register_node(node, f"dc{r % 3}")
        rpcs.append(RPCNode(node, contract, sps, layout, cache_chunksets=cache,
                            transport=BackboneTransport(sps, bb, node)))
    bb.register_node("client", "dc0")
    fleet = RPCFleet(rpcs, CacheAffinityPolicy(), backbone=bb)
    client = ShelbyClient(contract, fleet, deposit=1e9)
    rng = np.random.default_rng(7)
    metas = [client.put(rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes())
             for _ in range(12)]
    for n in rpcs:
        n._cache.clear()  # cold start: the PUT path warmed the writer's cache
    return fleet, client, metas


def test_replay_settlement_conserves_value_and_is_deterministic():
    _, _, metas = _replay_world()
    reqs = zipf_hotset(metas, clients=["client"], num_requests=1000,
                       read_bytes=64 * 1024, interarrival_ms=0.2, seed=11,
                       arrival="poisson")
    runs = []
    for _ in range(2):
        _, client, _ = _replay_world()
        with client.session(deposit_per_node=1e6) as session:
            receipts, result = session.replay(reqs)
        runs.append((session, receipts, result))

    assert runs[0][2].digest() == runs[1][2].digest()
    for session, receipts, _ in runs:
        # settled income equals the receipts' payments, node by node
        paid_by_node: dict[str, float] = {}
        for r in session.receipts:
            for nid, amount in r.payments.items():
                paid_by_node[nid] = paid_by_node.get(nid, 0.0) + amount
        income = session.settlement.node_income
        assert set(income) == set(paid_by_node)
        for nid, paid in paid_by_node.items():
            assert income[nid] == pytest.approx(paid, rel=1e-9)
        served = [r for r in receipts if r is not None and not r.shed]
        assert served
        assert all(r.total_paid > 0.0 for r in served)
        assert all(r.latency_ms >= 0.0 for r in served)
