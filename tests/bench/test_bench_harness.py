"""A run through the harness on the CPU: sound runs are correct, and every
fault planted under the timed path, the controls among them, is caught."""
import pytest

MIXES = ("degraded-scan", "put-stream")

# (mix, fault, the number that reads it): the controls (skip_decode,
# skip_parity) and the faults each cell can have: an answer altered where it
# is produced, half of it left out, a state left unchanged, one put's parity
# wrong, the decode moved to the host, a broken settlement, a refused request
FAULTS = [
    ("degraded-scan", "skip_decode", "wrong_reads"),
    ("degraded-scan", "flip_byte", "wrong_reads"),
    ("degraded-scan", "host_decode", "host_decodes"),
    ("degraded-scan", "half_read", "wrong_reads"),
    ("degraded-scan", "stale_read", "wrong_reads"),
    ("put-stream", "skip_parity", "wrong_readbacks"),
    ("put-stream", "flip_parity_once", "wrong_readbacks"),
    ("put-stream", "lose_put", "wrong_stored_chunks"),
    ("put-stream", "flip_stored", "wrong_stored_chunks"),
    ("degraded-scan", "double_charge", "settlement_violations"),
    ("degraded-scan", "refuse_reads", "failed_reads"),
    ("put-stream", "refuse_puts", "failed_puts"),
]


@pytest.mark.parametrize("mix", MIXES)
def test_sound_run_is_correct(run_tiny, mix):
    result = run_tiny(mix)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"] and len(result["metrics"]) == 2
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("mix,fault,number", FAULTS)
def test_planted_fault_is_caught(run_tiny, mix, fault, number):
    result = run_tiny(mix, fault=fault)
    assert not result["correct"], (fault, result["checks"])
    check = result["checks"][number]
    assert check["value"] > check["limit"], (fault, result["checks"])


def test_traced_run_reports_per_layer_metrics(run_tiny):
    result = run_tiny("degraded-scan", trace=True)
    assert result["correct"]
    metrics = result["metrics"]
    assert metrics["scan.gf_calls_per_chunkset"]["value"] > 0
    assert metrics["scan.compiles_in_window"]["value"] == 0
    assert "read_MBps" not in metrics
    # the CPU has no device plane: nothing to read, so no roofline and no busy share
    assert "gf_matmul_roofline" not in metrics
    assert result["device"]["busy_s"] == 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_every_read_of_the_scan_decodes_erased_data(run_tiny):
    result = run_tiny("degraded-scan")
    work = result["work"]
    assert work["crashed_sps"] >= 1
    assert work["chunkset_reads"] == result["attempted"]
    assert work["chunkset_reads_with_erased_data"] == work["chunkset_reads"]


def test_every_acknowledged_put_is_read_back(run_tiny):
    result = run_tiny("put-stream")
    work = result["work"]
    assert work["puts_acked"] == result["attempted"] > 0
    assert work["chunksets_read_back"] == 2 * work["puts_acked"]  # 100,000 bytes: 2 chunksets


def test_one_flipped_byte_makes_a_read_wrong():
    from bench import check, traffic

    source = bytes(range(256)) * 64
    req = traffic.Request(blob=0, offset=1000, length=5000)
    good = traffic.Done(req, 0.0, 0.1, source[1000:6000])
    flipped = bytearray(source[1000:6000])
    flipped[4321] ^= 0x01
    bad = traffic.Done(req, 0.1, 0.2, bytes(flipped))
    assert check.wrong_reads([good], [source]) == 0
    assert check.wrong_reads([good, bad], [source]) == 1
