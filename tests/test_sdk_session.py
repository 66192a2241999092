"""Fleet-first client sessions: pay-on-delivery, streaming reads, per-node
settlement, and conservation (§2.2 / §3.2 "reads are paid")."""
import numpy as np
import pytest

from repro.core.contract import ShelbyContract
from repro.core.payments import ChannelError
from repro.core.placement import SPInfo
from repro.net.fleet import CacheAffinityPolicy, RPCFleet
from repro.storage.rpc import ReadError, RPCNode
from repro.storage.sdk import ShelbyClient
from repro.storage.sp import StorageProvider


@pytest.fixture
def fleet_cluster(small_layout):
    """(contract, sps, fleet, client) — 3 RPC nodes over 8 SPs."""
    contract = ShelbyContract()
    sps = {}
    for i in range(8):
        contract.register_sp(SPInfo(sp_id=i, stake=1000.0, dc=f"dc{i % 3}", rack=f"r{i % 4}"))
        sps[i] = StorageProvider(i)
    rpcs = [
        RPCNode(f"rpc{r}", contract, sps, small_layout, cache_chunksets=16)
        for r in range(3)
    ]
    fleet = RPCFleet(rpcs, CacheAffinityPolicy())
    client = ShelbyClient(contract, fleet, deposit=1e6)
    return contract, sps, fleet, client


def _blob(rng, n=300_000):
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


# -- pay on delivery ---------------------------------------------------------------
def test_failed_read_never_debits_the_channel(fleet_cluster, rng):
    """Regression: `get` used to pay BEFORE the read, charging the client
    for ReadErrors."""
    contract, sps, fleet, client = fleet_cluster
    meta = client.put(_blob(rng))
    for ck in range(3):  # m + 1 = 3 chunks of chunkset 0 gone
        sps[meta.placement[(0, ck)]].crash()
    for rpc in fleet.rpcs:
        rpc._cache.clear()
    session = client.current_session
    paid_before = session.total_paid
    with pytest.raises(ReadError):
        client.get(meta.blob_id)
    assert session.total_paid == paid_before
    assert not session.receipts  # no receipt for a failed read


def test_successful_read_pays_and_receipts(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    data = _blob(rng)
    meta = client.put(data)
    receipt = client.read(meta.blob_id)
    assert receipt.data == data
    assert receipt.total_paid > 0
    assert receipt.payments  # at least one serving node got paid
    assert set(receipt.payments) <= set(fleet.node_ids)
    assert sum(receipt.chunksets_by_node.values()) == meta.num_chunksets


def test_channels_open_lazily_per_serving_node(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    data = _blob(rng)
    meta = client.put(data)
    session = client.current_session
    assert not session.channels  # nothing read yet -> no channels
    receipt = session.read(meta.blob_id)
    assert set(session.channels) == set(receipt.payments)


# -- settlement conservation -------------------------------------------------------
def test_settlement_conservation_multi_node(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    metas = [client.put(_blob(rng)) for _ in range(3)]
    with client.session() as session:
        for meta in metas:
            session.read(meta.blob_id)
            session.read(meta.blob_id, 1000, 50_000)
    s = session.settlement
    assert s is not None
    # every serving node settled; refund + income == deposit, per channel
    for rpc_id, dep in s.deposits.items():
        assert s.client_refunds[rpc_id] + s.node_income[rpc_id] == pytest.approx(dep)
    assert s.total_refunded + s.total_node_income == pytest.approx(s.total_deposited)
    # per-node settlement totals match the ReadReceipt payment sums
    paid = {}
    for r in session.receipts:
        for rpc_id, amt in r.payments.items():
            paid[rpc_id] = paid.get(rpc_id, 0.0) + amt
    assert set(paid) == set(s.node_income)
    for rpc_id in paid:
        assert s.node_income[rpc_id] == pytest.approx(paid[rpc_id], abs=1e-6)
    # the RPC->SP cascade realized every accrued micropayment
    assert sum(s.sp_income.values()) == pytest.approx(
        sum(sp.settled_income for sp in sps.values())
    )
    assert sum(s.sp_income.values()) > 0


def test_stale_refund_rejected_at_settlement(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    meta = client.put(_blob(rng))
    session = client.session()
    session.read(meta.blob_id, 0, 1000)
    rpc_id, channel = next(iter(session.channels.items()))
    stale = channel.latest_refund
    session.read(meta.blob_id, 1000, 200_000)  # fresher refunds signed
    assert channel.latest_refund.seq > stale.seq
    # an uncooperative party broadcasting the stale refund on the OPEN
    # channel is preempted by the fresher one (§3.2 seq check)...
    with pytest.raises(ChannelError, match="stale"):
        channel.settle(stale)
    # ...which leaves the channel un-settled, so the honest close succeeds
    s = session.close()
    assert s.node_income[rpc_id] == pytest.approx(channel.paid)
    # and after settlement ANY further broadcast (stale or not) is rejected
    with pytest.raises(ChannelError):
        channel.settle(stale)


def test_reads_after_close_rejected_and_close_idempotent(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    meta = client.put(_blob(rng))
    session = client.session()
    session.read(meta.blob_id)
    first = session.close()
    assert session.close() is first
    with pytest.raises(ChannelError):
        session.read(meta.blob_id)


def test_sp_income_flows_only_at_settlement(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    meta = client.put(_blob(rng))
    session = client.session()
    session.read(meta.blob_id)
    assert all(sp.settled_income == 0.0 for sp in sps.values())
    accrued = sum(sp.earned_reads for sp in sps.values())
    assert accrued > 0  # micropayments accrued on delivery...
    s = session.close()
    # ...and realized exactly at settlement
    assert sum(s.sp_income.values()) == pytest.approx(accrued)


# -- streaming ---------------------------------------------------------------------
def test_blob_reader_is_seekable_file_like(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    data = _blob(rng)
    meta = client.put(data)
    with client.open(meta.blob_id) as f:
        assert f.readable() and f.seekable()
        assert f.read(100) == data[:100]
        assert f.tell() == 100
        f.seek(50_000)
        assert f.read(64) == data[50_000:50_064]
        f.seek(-100, 2)
        assert f.read() == data[-100:]
        assert f.read() == b""  # EOF
        f.seek(10, 1)  # relative seek past EOF is fine; reads return b""
        assert f.read(5) == b""
        with pytest.raises(ValueError):
            f.seek(0, 3)  # invalid whence, file-like contract
    with pytest.raises(ValueError):
        f.read(1)  # closed


def test_stream_yields_receipts_covering_the_blob(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    data = _blob(rng)
    meta = client.put(data)
    receipts = list(client.stream(meta.blob_id, chunk_size=70_000))
    assert b"".join(r.data for r in receipts) == data
    assert all(len(r.data) <= 70_000 for r in receipts)
    offsets = [r.offset for r in receipts]
    assert offsets == sorted(offsets)  # sequential


# -- batched reads -----------------------------------------------------------------
def test_get_many_routes_all_ranges_in_one_pass(fleet_cluster, rng):
    contract, sps, fleet, client = fleet_cluster
    d1, d2 = _blob(rng), _blob(rng, 150_000)
    m1, m2 = client.put(d1), client.put(d2)
    reads_before = fleet.chunkset_reads
    receipts = client.get_many(
        [(m1.blob_id, 0, 1000), (m1.blob_id, 100_000, None), (m2.blob_id, 0, None)]
    )
    assert receipts[0].data == d1[:1000]
    assert receipts[1].data == d1[100_000:]
    assert receipts[2].data == d2
    # chunksets shared between ranges are routed (and fetched) only once
    unique = set()
    lay = client.layout
    for bid, off, ln in [(m1.blob_id, 0, 1000), (m1.blob_id, 100_000, len(d1) - 100_000),
                         (m2.blob_id, 0, len(d2))]:
        first, last = lay.byte_range_to_chunksets(off, ln)
        unique |= {(bid, cs) for cs in range(first, last + 1)}
    assert fleet.chunkset_reads - reads_before == len(unique)


def test_single_node_client_is_a_fleet_of_one(cluster, rng):
    contract, sps, rpc, client = cluster
    data = _blob(rng)
    meta = client.put(data)
    assert client.fleet.node_ids == [rpc.rpc_id]
    receipt = client.read(meta.blob_id)
    assert receipt.data == data
    assert list(receipt.payments) == [rpc.rpc_id]
    s = client.settle()
    assert s.node_income[rpc.rpc_id] == pytest.approx(receipt.total_paid, abs=1e-6)
    assert rpc.serving_income == pytest.approx(s.node_income[rpc.rpc_id])


# -- simulation wiring -------------------------------------------------------------
def test_run_sim_credits_sps_through_settled_channels():
    from repro.core.simulation import honest_population, run_sim

    res = run_sim(
        honest_population(8), epochs=1, num_blobs=2, blob_bytes=100_000,
        num_rpcs=3, read_requests_per_epoch=12,
    )
    assert res.bytes_served > 0
    assert sum(res.sp_serving_income.values()) > 0
    assert res.client_read_payments > 0
    # per-node settlement totals == what the client's receipts paid
    assert sum(res.rpc_serving_income.values()) == pytest.approx(
        res.client_read_payments, abs=1e-5
    )


def test_decode_matmul_config_resolution():
    import jax

    from repro.configs.shelby import CONFIG
    from repro.kernels import ops
    from repro.kernels.ops import resolve_decode_matmul

    assert resolve_decode_matmul("numpy") is None
    assert resolve_decode_matmul("pallas") is ops.gf_matmul_np
    auto = resolve_decode_matmul("auto")
    if jax.default_backend() == "tpu":
        assert auto is ops.gf_matmul_np
    else:
        assert auto is None  # defaults to the numpy GF path on CPU
    assert CONFIG.resolve_decode_matmul() is auto
    with pytest.raises(ValueError):
        resolve_decode_matmul("cuda")
