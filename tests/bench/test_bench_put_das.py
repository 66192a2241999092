"""The block stream through the harness on the CPU at a 32 x 32 square: a sound
run reads 0 on every DAS check, and each check reads more than 0 under a fault
the test plants in the program for the length of the window."""
import json

import numpy as np
import pytest

DAS_K = 16  # a (16, 16) coefficient matrix: the bit-matrix kernel's smallest


def _das_checkout(dest, tiny_checkout):
    root = tiny_checkout(dest)
    config = json.loads((root / "bench/configs/tiny.json").read_text())
    das = json.loads((root / "bench/configs/shelby-das-128.json").read_text())
    config.update(name="tiny-das", das_k=DAS_K, guarantees=dict(
        config["guarantees"], das_square_exact=das["guarantees"]["das_square_exact"],
        das_on_device=True))
    (root / "bench/configs/tiny-das.json").write_text(json.dumps(config))
    mix = json.loads((root / "bench/mixes/block-stream.json").read_text())
    mix["put_bytes"] = DAS_K * DAS_K * config["das_share_bytes"]  # one square, 2 chunksets
    (root / "bench/mixes/block-stream.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-das", "source": "tests", "reduced": [],
                             "file": "bench/configs/tiny-das.json", "why": "tests"})
    bench["workloads"].append({"name": "tiny-das.block-stream", "config": "tiny-das",
                               "traffic": "block-stream", "chips": 1, "why": "tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "shelby-das-128.block-stream" in metric.get("workloads", []):
            metric["workloads"].append("tiny-das.block-stream")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def das_root(tmp_path, make_checkout):
    return _das_checkout(tmp_path, make_checkout)


@pytest.fixture
def run_das(das_root, compiles):
    import jax

    from bench import faults, run

    def go(fault=None, plant=None, seed=2**31 + 15, trace=False):
        spec = run.prepare(das_root, "tiny-das.block-stream")
        window = faults._patched(faults._in_window(plant)) if plant else faults.planted(fault)
        with window:
            return run.run_cell(spec, jax.devices()[:1], seed, 0.3, trace, compiles,
                                root=das_root)

    return go


DAS_CHECKS = ("wrong_das_data", "wrong_das_lines", "wrong_das_roots", "wrong_das_extension",
              "host_das_extensions")


def test_sound_block_stream_reads_zero_on_every_check(run_das):
    result = run_das()
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    for name in DAS_CHECKS + ("wrong_readbacks", "wrong_stored_chunks", "failed_puts"):
        assert result["checks"][name] == {"value": 0, "limit": 0}
    work = result["work"]
    assert work["das_side"] == 2 * DAS_K
    assert work["das_squares_extended"] == work["puts_acked"] + 1  # and the set-up put
    assert work["das_shares_placed"] == work["das_squares_extended"] * (2 * DAS_K) ** 2
    assert set(result["metrics"]) == {"write_MBps", "setup_s"}


def test_traced_block_stream_leaves_out_what_the_cpu_cannot_read(run_das):
    result = run_das(trace=True)
    assert result["correct"], result["checks"]
    # no device plane on the CPU: the kernel's program time is nothing to read
    assert "das.extend_device_ms_per_put" not in result["metrics"]
    assert "gf_bitmatmul_roofline" not in result["metrics"]
    assert "write.host_gap_ms_per_chunkset" in result["metrics"]


def _flip_data_share(patch):
    from repro.core.extend2d import Extend2D

    real = Extend2D.pad_square

    def pad_square(self, data, share_bytes):
        square = real(self, data, share_bytes).copy()
        square[1, 3, 5] ^= 0x01
        return square

    patch(Extend2D, "pad_square", pad_square)


def _zero_parity(patch):
    from repro.core.extend2d import Extend2D

    real = Extend2D.extend_batch

    def extend_batch(self, squares, matmul=None):
        out = real(self, squares, matmul=matmul)
        for ext in out:
            ext[self.k:] = 0
            ext[:, self.k:] = 0
        return out

    patch(Extend2D, "extend_batch", extend_batch)


def _flip_parity_share_once(patch):
    from repro.core.extend2d import Extend2D

    real = Extend2D.extend_batch
    calls = [0]

    def extend_batch(self, squares, matmul=None):
        out = real(self, squares, matmul=matmul)
        calls[0] += 1
        if calls[0] == 1:
            out[0][self.k + 1, 2, 7] ^= 0x01  # column 2's parity; its commitments match
        return out

    patch(Extend2D, "extend_batch", extend_batch)


def _wrong_root(patch):
    import dataclasses

    from repro.core.contract import ShelbyContract

    real = ShelbyContract.register_das

    def register_das(self, record):
        bad = bytes([record.das_root[0] ^ 0x01]) + record.das_root[1:]
        return real(self, dataclasses.replace(record, das_root=bad))

    patch(ShelbyContract, "register_das", register_das)


def _extension_on_host(patch):
    from repro.storage import das

    real = das.extend_and_disperse_many

    def extend_and_disperse_many(contract, sps, blobs, spec, *, matmul=None):
        return real(contract, sps, blobs, spec, matmul=None)

    patch(das, "extend_and_disperse_many", extend_and_disperse_many)


@pytest.mark.parametrize("plant,number,exactly", [
    (_flip_data_share, "wrong_das_data", None),
    (_zero_parity, "wrong_das_lines", None),
    (_zero_parity, "wrong_das_extension", 3 * DAS_K * DAS_K),
    (_flip_parity_share_once, "wrong_das_lines", 2),  # one row and one column
    (_flip_parity_share_once, "wrong_das_extension", 1),
    (_wrong_root, "wrong_das_roots", None),
    (_extension_on_host, "host_das_extensions", None),
])
def test_planted_das_fault_is_caught(run_das, plant, number, exactly):
    result = run_das(plant=plant)
    assert not result["correct"], (plant.__name__, result["checks"])
    value = result["checks"][number]["value"]
    assert value > 0, (plant.__name__, result["checks"])
    if exactly is not None:
        assert value == exactly, (plant.__name__, result["checks"])


def test_a_host_extension_is_counted_when_later_puts_repeat_its_blob(das_root, run_das):
    """Blob indices repeat in a cycle: each put is judged by its own blob id."""
    mix = json.loads((das_root / "bench/mixes/block-stream.json").read_text())
    (das_root / "bench/mixes/block-stream.json").write_text(json.dumps(dict(mix, stored_blobs=1)))

    def first_extension_on_host(patch):
        from repro.storage import das

        real = das.extend_and_disperse_many
        calls = [0]

        def extend_and_disperse_many(contract, sps, blobs, spec, *, matmul=None):
            calls[0] += 1
            return real(contract, sps, blobs, spec, matmul=None if calls[0] == 1 else matmul)

        patch(das, "extend_and_disperse_many", extend_and_disperse_many)

    result = run_das(plant=first_extension_on_host)
    assert result["attempted"] >= 2
    assert result["checks"]["host_das_extensions"]["value"] == 1


def test_the_mix_control_is_caught(das_root, run_das):
    mix = json.loads((das_root / "bench/mixes/block-stream.json").read_text())
    result = run_das(fault=mix["control"])
    assert not result["correct"]
    assert result["checks"]["wrong_readbacks"]["value"] > 0


def test_a_program_without_a_wide_kernel_refuses_to_set_up(das_root, monkeypatch):
    from bench import parts
    from repro.kernels import ops

    monkeypatch.delattr(ops, "uses_bit_matrix")
    spec = json.loads((das_root / "bench/configs/tiny-das.json").read_text())
    mix = json.loads((das_root / "bench/mixes/block-stream.json").read_text())
    op = parts.load(das_root, "ops", "put_das")

    class Dep:  # set-up refuses before it touches the deployment
        config = dict(spec, das_k=128)

    with pytest.raises(RuntimeError, match="wide coefficient matrices"):
        op.Op(Dep(), mix, 1, print)


def test_syndrome_test_finds_a_bad_line_and_passes_codewords():
    from bench import das_reference as ref

    rng = np.random.default_rng(3)
    k = 8
    square = rng.integers(0, 256, (k, k, 16), dtype=np.uint8)
    ext = ref.extend(square)
    h = ref.parity_check(2 * k, k)
    g = ref.matmul(rng.integers(0, 256, (4, k), dtype=np.uint8), h)
    assert not ref.matmul(g, ext.reshape(2 * k, -1)).any()
    ext[k + 2, 5, 9] ^= 0x40
    syn = ref.matmul(g, ext.reshape(2 * k, -1)).reshape(4, 2 * k, 16)
    assert np.flatnonzero(syn.any(axis=(0, 2))).tolist() == [5]
