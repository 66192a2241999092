"""simsan: the runtime sanitizer for the event loop.

Injected-fault coverage (the acceptance criteria of ISSUE 10): a leaked
resource slot and an off-loop resource mutation are each caught with an
error naming the task and sim-time; a cancelled task's slots are
reclaimed (regression for the narrowed GeneratorExit handling); the
pop-order audit, payment-conservation check, and — crucially — that a
sanitized run is behaviourally identical to an unsanitized one (same
digest), so CI can run smokes under SHELBY_SIMSAN=1 for free.
"""
import numpy as np
import pytest

from repro.analysis.simsan import SanitizerError, check_payment_conservation
from repro.core.contract import ShelbyContract
from repro.core.placement import SPInfo
from repro.net.backbone import Backbone
from repro.net.events import Acquire, EventLoop, Release, Sleep
from repro.net.fleet import CacheAffinityPolicy, RPCFleet
from repro.net.workloads import replay_open_loop, zipf_hotset
from repro.storage.blob import BlobLayout
from repro.storage.rpc import BackboneTransport, RPCNode
from repro.storage.sdk import ShelbyClient
from repro.storage.sp import ServiceSpec, StorageProvider


# -- injected fault (a): leaked resource slot ------------------------------------
def test_leaked_slot_detected_at_drain():
    loop = EventLoop(sanitize=True)

    def leaker():
        yield Acquire(("sp", 3), 2)
        yield Sleep(5.0)
        # returns while still holding the slot

    loop.spawn(leaker(), label="reader/blob7")
    with pytest.raises(SanitizerError) as err:
        loop.run()
    msg = str(err.value)
    assert "leak" in msg
    assert "('sp', 3)" in msg          # resource key
    assert "reader/blob7" in msg       # holder task
    assert "t=0" in msg                # acquire sim-time

    # same program on an unsanitized loop: silent (that's the point)
    loop2 = EventLoop()

    def leaker2():
        yield Acquire(("sp", 3), 2)
        yield Sleep(5.0)

    loop2.spawn(leaker2(), label="reader/blob7")
    loop2.run()
    assert loop2.resource(("sp", 3)).in_use == 1


def test_release_without_acquire_detected():
    loop = EventLoop(sanitize=True)

    def over_releaser():
        yield Sleep(2.0)
        yield Release(("disk", 0))

    loop.spawn(over_releaser(), label="over/0")
    with pytest.raises(SanitizerError, match="release without acquire"):
        loop.run()
    msg_loop = EventLoop(sanitize=True)
    try:
        def again():
            yield Sleep(2.0)
            yield Release(("disk", 0))
        msg_loop.spawn(again(), label="over/1")
        msg_loop.run()
    except SanitizerError as e:
        assert "over/1" in str(e) and "t=2" in str(e)


# -- injected fault (b): off-loop mutation ---------------------------------------
def test_off_loop_scalar_mutation_names_task_and_time():
    loop = EventLoop(sanitize=True)

    def mutator():
        yield Sleep(7.0)
        res = loop.resource(("sp", 1), 4)
        res.in_use += 1  # bypassing Acquire

    loop.spawn(mutator(), label="rogue/writer")
    with pytest.raises(SanitizerError) as err:
        loop.run()
    msg = str(err.value)
    assert "off-loop mutation" in msg
    assert "rogue/writer" in msg       # the task
    assert "t=7" in msg                # the sim-time
    assert "in_use" in msg and "('sp', 1)" in msg


def test_off_loop_dict_mutation_detected_in_window():
    loop = EventLoop(sanitize=True)

    def legit():
        yield Acquire(("sp", 2), 4)
        yield Sleep(1.0)
        yield Release(("sp", 2))

    def rogue():
        yield Sleep(3.0)
        # dict-valued accounting can't be guarded by __setattr__; the
        # shadow check catches it at the next engine touch / drain
        loop.resource(("sp", 2)).in_use_by_class[0] += 1

    loop.spawn(legit(), label="legit")
    loop.spawn(rogue(), label="rogue/dict")
    with pytest.raises(SanitizerError) as err:
        loop.run()
    msg = str(err.value)
    assert "off-loop mutation" in msg and "in_use_by_class" in msg
    assert "('sp', 2)" in msg


# -- regression: a cancelled task never leaks its slots --------------------------
def test_cancelled_task_slots_are_reclaimed():
    loop = EventLoop(sanitize=True)
    granted_at = []

    def holder():
        yield Acquire(("disk", 0), 1)
        yield Sleep(100.0)
        yield Release(("disk", 0))

    def waiter():
        yield Acquire(("disk", 0), 1)
        granted_at.append(loop.now)
        yield Release(("disk", 0))

    h = loop.spawn(holder(), label="holder")

    def canceller():
        yield Sleep(5.0)
        h.cancel()

    loop.spawn(waiter(), at_ms=1.0, label="waiter")
    loop.spawn(canceller(), label="canceller")
    # a leak would deadlock the waiter AND trip the sanitizer at drain;
    # instead the cancel hands the slot over at t=5
    loop.run()
    assert granted_at == [5.0]
    assert loop.resource(("disk", 0)).in_use == 0
    assert h.held == []


def test_cancel_reclaim_works_without_sanitizer():
    loop = EventLoop()

    def holder():
        yield Acquire(("disk", 0), 1)
        yield Sleep(100.0)

    h = loop.spawn(holder(), label="holder")

    def canceller():
        yield Sleep(5.0)
        h.cancel()

    loop.spawn(canceller(), label="canceller")
    loop.run()
    assert loop.resource(("disk", 0)).in_use == 0


# -- pop-order / causality audit -------------------------------------------------
def test_pop_order_audit_unit():
    loop = EventLoop(sanitize=True)
    san = loop._san
    san.on_pop(5.0, 10)
    with pytest.raises(SanitizerError, match="same-timestamp"):
        san.on_pop(5.0, 10)  # seq must strictly ascend within a timestamp
    with pytest.raises(SanitizerError, match="backwards"):
        san.on_pop(4.0, 11)
    with pytest.raises(SanitizerError, match="non-finite"):
        san.on_push(float("nan"), type("H", (), {"label": "x"})())


def test_scheduling_into_the_past_is_a_causality_violation():
    loop = EventLoop(sanitize=True)

    def child():
        yield Sleep(0.0)

    def parent():
        yield Sleep(10.0)
        loop.spawn(child(), at_ms=1.0, label="too-late")

    loop.spawn(parent(), label="parent")
    with pytest.raises(SanitizerError, match="causality"):
        loop.run()


# -- sanitize must not perturb behaviour -----------------------------------------
def _world(num_sps=6, slots=4):
    layout = BlobLayout(k=4, m=2, chunkset_bytes_target=64 * 1024)
    contract = ShelbyContract()
    bb = Backbone.mesh(3, base_latency_ms=4.0, gbps=10.0)
    sps = {}
    for i in range(num_sps):
        contract.register_sp(SPInfo(sp_id=i, stake=1000.0, dc=f"dc{i % 3}"))
        sps[i] = StorageProvider(i, service=ServiceSpec(slots=slots))
        bb.register_node(f"sp{i}", f"dc{i % 3}")
    bb.register_node("rpc0", "dc0")
    rpc = RPCNode("rpc0", contract, sps, layout,
                  transport=BackboneTransport(sps, bb, "rpc0"))
    bb.register_node("client", "dc0")
    fleet = RPCFleet([rpc], CacheAffinityPolicy(), backbone=bb)
    client = ShelbyClient(contract, fleet, deposit=1e9)
    return fleet, client


def _digest_of_replay(monkeypatch, sanitized: bool) -> str:
    if sanitized:
        monkeypatch.setenv("SHELBY_SIMSAN", "1")
    else:
        monkeypatch.delenv("SHELBY_SIMSAN", raising=False)
    fleet, client = _world()
    rng = np.random.default_rng(0)
    metas = [client.put(rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes())
             for _ in range(3)]
    reqs = zipf_hotset(metas, clients=["client"], num_requests=60, seed=11)
    result = replay_open_loop(fleet, reqs)
    assert all(r.ok for r in result.records)
    return result.digest()


def test_sanitized_replay_digest_identical(monkeypatch):
    """EventLoop(sanitize=True) observes; it must never move an event —
    the determinism digest of a sanitized replay equals the plain one."""
    assert (_digest_of_replay(monkeypatch, sanitized=True)
            == _digest_of_replay(monkeypatch, sanitized=False))


# -- payment conservation --------------------------------------------------------
class _Chan:
    def __init__(self, deposit, paid):
        self.deposit = deposit
        self.paid = paid


class _Receipt:
    def __init__(self, payments):
        self.payments = payments


class _Session:
    def __init__(self, receipts, channels):
        self.receipts = receipts
        self.channels = channels


def test_payment_conservation_clean():
    session = _Session(
        receipts=[_Receipt({"rpc0": 0.25}), _Receipt({"rpc0": 0.25, "rpc1": 0.1})],
        channels={"rpc0": _Chan(10.0, 0.5), "rpc1": _Chan(10.0, 0.1)},
    )
    check_payment_conservation(session)  # no raise


def test_payment_conservation_catches_unreceipted_debit():
    session = _Session(
        receipts=[_Receipt({"rpc0": 0.25})],
        channels={"rpc0": _Chan(10.0, 0.40)},  # 0.15 paid with no receipt
    )
    with pytest.raises(SanitizerError, match="payment conservation"):
        check_payment_conservation(session, where="epoch 1")
    with pytest.raises(SanitizerError, match="epoch 1"):
        check_payment_conservation(session, where="epoch 1")


def test_payment_conservation_catches_receipt_without_channel():
    session = _Session(receipts=[_Receipt({"ghost": 0.1})], channels={})
    with pytest.raises(SanitizerError, match="no\\s+channel"):
        check_payment_conservation(session)


def test_run_sim_per_epoch_conservation_wired():
    from repro.core.simulation import run_sim
    from repro.storage.sp import SPBehavior
    res = run_sim({i: SPBehavior() for i in range(6)}, epochs=1,
                  read_requests_per_epoch=20, seed=5, sanitize=True)
    assert res.client_read_payments > 0
