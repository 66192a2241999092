"""The benchmark's files: BENCHMARK.json keeps to its contract, every
configuration derives its source's layout, every mix is a pure function of
its seed, the peaks table and the gf_matmul work function."""
import itertools
import json
from pathlib import Path
import re

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]

from bench import deploy, parts, reading, traffic
from bench.metrics.gf_matmul_work import algorithm_bytes

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
MIX_NAMES = sorted(p.stem for p in (REPO / "bench/mixes").glob("*.json"))


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"])) == \
        len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (REPO / path).is_dir()
    assert (REPO / BENCH["command"][1]).is_file()


def test_every_cell_reports_what_the_contract_asks():
    from bench import run

    used = set()
    for cell in BENCH["workloads"]:
        assert cell["config"] in CONFIGS and cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"]) and len(cell["why"]) <= 200
        used.add(cell["config"])
        spec = {"bench": BENCH, "cell": cell}
        e2e = [m["name"] for m in run.reported(spec, trace=False)]
        assert "setup_s" in e2e and len(e2e) >= 2, (cell["name"], e2e)
        assert all(name in run.END_TO_END for name in e2e)
        per_layer = run.reported(spec, trace=True)
        assert per_layer, cell["name"]
        for m in per_layer:
            assert m["moves"] in e2e
    assert used == set(CONFIGS)
    for name in MIX_NAMES:
        mix = json.loads((REPO / f"bench/mixes/{name}.json").read_text())
        for kind, key in (("ops", "op"), ("plans", "plan"), ("loops", "loop")):
            assert (REPO / "bench" / kind / f"{mix[key]}.py").is_file(), (name, key)
    for m in BENCH["per_layer"]:
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
        assert all(w in {c["name"] for c in BENCH["workloads"]} for w in m["workloads"])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_derives_its_sources_layout(name):
    config = json.loads((REPO / CONFIGS[name]["file"]).read_text())
    assert config["name"] == name
    assert set(config["reduced"]) == set(CONFIGS[name]["reduced"])
    assert all(key in config for key in config["reduced"])
    deploy.check_layout(config)  # raises where the program derives another layout
    lay = deploy.layout_of(config)
    assert (lay.n, lay.k, lay.code.alpha, lay.w) == (config["n"], config["k"],
                                                    config["alpha"], config["w"])
    assert config["d"] == lay.code.d
    assert config["guarantees"]["decode_on_device"] is True


def test_config_layouts_are_the_sources():
    shelby = json.loads((REPO / "bench/configs/shelby-10-6.json").read_text())
    clay = json.loads((REPO / "bench/configs/clay-20-16.json").read_text())
    assert (shelby["n"], shelby["k"], shelby["alpha"], shelby["w"]) == (16, 10, 216, 4856)
    assert (clay["n"], clay["k"], clay["alpha"], clay["w"]) == (20, 16, 1024, 640)
    bad = dict(shelby, w=4096)
    with pytest.raises(ValueError):
        deploy.check_layout(bad)


@pytest.mark.parametrize("mix_name", MIX_NAMES)
def test_mix_is_a_pure_function_of_its_seed(mix_name):
    mix = json.loads((REPO / f"bench/mixes/{mix_name}.json").read_text())
    cs = 10 * 2**20
    seed, other = 2**31 + 11, 5

    def first(s, count=40):
        it = iter(traffic.plan(REPO, mix, cs, s))
        return [next(it) for _ in range(count)]

    assert first(seed) == first(seed)
    # another seed: the same sizes and offsets
    assert sorted((r.blob, r.offset, r.length) for r in first(seed)) == \
        sorted((r.blob, r.offset, r.length) for r in first(other))
    if mix["op"] == "put":
        assert traffic.put_blob(mix, seed, 3) == traffic.put_blob(mix, seed, 3)
        assert traffic.put_blob(mix, seed, 3) != traffic.put_blob(mix, other, 3)
        assert traffic.put_blob(mix, seed, 3) != traffic.put_blob(mix, seed, 4)
        return
    small = dict(mix, blob_chunksets=1, stored_blobs=1)
    assert traffic.stored_blobs(small, 4096, seed) == traffic.stored_blobs(small, 4096, seed)
    assert traffic.stored_blobs(small, 4096, seed) != traffic.stored_blobs(small, 4096, other)
    reqs = first(seed)
    total = mix["stored_blobs"] * mix["blob_chunksets"]
    assert reqs[:total] == reqs[total:2 * total]
    assert len({(r.blob, r.offset) for r in reqs}) == total


def _placements(sps, n, chunksets, seed=3):
    rng = np.random.default_rng(seed)
    return [dict(enumerate(rng.choice(sps, n, replace=False).tolist())) for _ in range(chunksets)]


@pytest.mark.parametrize("sps,k,m,erased,chunksets", [
    (24, 10, 6, 1, 16), (24, 10, 6, 2, 16), (24, 16, 4, 1, 16), (8, 4, 2, 1, 4)])
def test_erasing_victims_are_the_fewest_that_erase_every_chunkset(sps, k, m, erased, chunksets):
    placements = _placements(sps, k + m, chunksets)
    victims = deploy.erasing_victims(placements, k, m, erased)
    down = set(victims)
    for p in placements:
        assert sum(p[ck] in down for ck in range(k)) >= erased
        assert sum(sp in down for sp in p.values()) <= m
    fewer = [set(c) for c in itertools.combinations(range(sps), len(victims) - 1)]
    assert not any(all(sum(p[ck] in v for ck in range(k)) >= erased for p in placements)
                   and all(sum(sp in v for sp in p.values()) <= m for p in placements)
                   for v in fewer)


def test_erasing_no_data_chunk_crashes_nothing():
    assert deploy.erasing_victims(_placements(24, 16, 4), 10, 6, 0) == []
    with pytest.raises(ValueError):  # 3 erased data chunks in every chunkset of (4,2): none
        deploy.erasing_victims(_placements(8, 6, 16), 4, 2, 3)


def test_parts_are_found_by_name():
    assert parts.load(REPO, "plans", "chunkset_cycle").requests
    with pytest.raises(FileNotFoundError):
        parts.load(REPO, "plans", "no_such_plan")


def test_peaks_table():
    row = reading.peaks(REPO, "TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9 and row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12 and row["hbm_bytes"] == 16e9
    assert "Google Cloud" in row["source"]
    with pytest.raises(KeyError):
        reading.peaks(REPO, "TPU v9 imaginary")
    r = reading.Reading(done=[], counters={}, kernel_shapes=[], root=REPO,
                        device_kind="cpu")
    with pytest.raises(KeyError):
        r.peak


@pytest.mark.parametrize("shape,want", [
    # (10,6) decode: 6 unknowns from 12 known over one chunkset's planes
    ((6, 12, 1048896), 6 * 12 + 12 * 1048896 + 6 * 1048896),
    ((6, 12, 1048896), 18_880_200),
    # a 2-erasure IS group at (10,6)
    ((2, 16, 699264), 32 + 11_188_224 + 1_398_528),
    # (20,16) decode: 4 unknowns from 16 known over 256 planes of w=640
    ((4, 16, 256 * 640), 64 + 2_621_440 + 655_360),
    # the DAS (4,4) extension of one put
    ((4, 4, 4096), 16 + 16_384 + 16_384),
])
def test_gf_matmul_algorithm_bytes(shape, want):
    assert algorithm_bytes(*shape) == want
