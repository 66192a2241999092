#!/usr/bin/env python3
"""Smoke run of the Shelby read path on TPU chips.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four RPC replicas, one per chip

One chip: the Pallas ``gf_matmul`` is checked byte for byte against the
numpy GF(2^8) path at the decode shapes of the paper's layout, then a
deployment at that layout ((10,6) Clay, 10 MiB chunksets, 24 SPs, two RPC
nodes behind an ``RPCFleet``) takes 8 x 33 MiB of seeded blobs, loses two
SPs that hold data chunks, and serves ranged reads, a streaming read and
one ``get_many`` over every blob.  Every byte read must equal what was
written, settlement must conserve value, and every decode must have run
through the kernel on the chip.

``--chips 4`` runs only the replica phase: the same workload behind four
RPC nodes, node i decoding on chip i, and every chip must serve decodes.

The lines before the last are one smoke run's record, not benchmark
metrics.  The last line is one JSON object naming the device.  Without a
TPU the script exits non-zero and prints no result; it never falls back to
the CPU.  JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` here.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

MIB = 1024 * 1024
NUM_BLOBS = 8
BLOB_BYTES = 33 * MIB  # not a whole number of 10 MiB chunksets
CRASHED_SPS = 2
SEED = 0
# (M, K, N) of decode matmuls at the paper's layout (alpha=216, w=4856):
# a k-of-n read (6 erasures, 12 known nodes), a 2-erasure IS group, 6
# unknowns over a chunkset's planes, one wide stacked solve, and the DAS
# extension's (4,4) square
PARITY_SHAPES = [(6, 12, 1048896), (2, 16, 699264), (6, 16, 1048464),
                 (1, 16, 16 * 699264), (4, 4, 4096)]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def tpu_devices(chips: int):
    """The TPU devices JAX sees, at least ``chips`` of them, or exit."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX's devices are {devices}")
    if len(devices) < chips:
        fail(f"{chips} chips asked for, JAX sees {len(devices)}")
    return devices


def kernel_parity(device, rng) -> None:
    """gf_matmul on the chip == numpy GF(2^8) at the decode shapes."""
    import numpy as np

    from repro.core import gf
    from repro.kernels import ops

    for m, k, n in PARITY_SHAPES:
        a = rng.integers(0, 256, (m, k), dtype=np.uint8)
        b = rng.integers(0, 256, (k, n), dtype=np.uint8)
        got = ops.gf_matmul_np(a, b, device=device)
        check(np.array_equal(got, gf.matmul_np(a, b)),
              f"gf_matmul ({m},{k})x({k},{n}) differs from the numpy GF path")
        print(f"parity: gf_matmul ({m},{k})x({k},{n}) bytes equal numpy")


def serve(num_rpcs: int, rng) -> dict:
    """Write seeded blobs through a deployment of ``num_rpcs`` RPC nodes,
    crash SPs that hold data chunks, read everything back, and check it.
    Returns the decode traffic per device."""
    from repro.configs.shelby import CONFIG
    from repro.kernels import ops
    from repro.launch.train import build_cluster

    layout, num_blobs, blob_bytes = CONFIG.layout, NUM_BLOBS, BLOB_BYTES
    contract, sps, _, client = build_cluster(
        num_sps=CONFIG.num_sps, layout=layout, num_rpcs=num_rpcs)
    fleet = client.fleet
    blobs = [rng.bytes(blob_bytes) for _ in range(num_blobs)]

    t0 = time.perf_counter()
    metas = [client.put(data, payment=1.0, epochs=10) for data in blobs]
    print(f"write: {num_blobs} blobs x {blob_bytes} bytes at ({layout.k},{layout.m}), "
          f"{layout.chunkset_bytes} B chunksets, {num_rpcs} RPC nodes: "
          f"{time.perf_counter() - t0} s wall")

    victims = sorted({metas[0].placement[(0, ck)] for ck in range(layout.k)})[:CRASHED_SPS]
    for sp_id in victims:
        sps[sp_id].crash()
    print(f"crashed SPs {victims}, which hold data chunks")

    ops.reset_gf_traffic()
    session = client.session()
    t0 = time.perf_counter()
    cs = layout.chunkset_bytes
    ranges = [(0, 123, 4096), (1, cs - 1000, 2000), (2, blob_bytes - 5000, 5000)]
    for i, off, length in ranges:
        got = session.read(metas[i].blob_id, off, length).data
        check(got == blobs[i][off:off + length], f"ranged read of blob {i} differs")
    with session.open(metas[3].blob_id) as reader:
        streamed = b"".join(iter(lambda: reader.read(cs // 3), b""))
    check(streamed == blobs[3], "streaming read of blob 3 differs")
    t1 = time.perf_counter()
    receipts = session.get_many([(m.blob_id, 0, None) for m in metas])
    t2 = time.perf_counter()
    for i, receipt in enumerate(receipts):
        check(receipt.data == blobs[i], f"get_many: blob {i} differs")
    print(f"read: {len(ranges)} ranged reads + 1 streaming read {t1 - t0} s wall, "
          f"get_many over {num_blobs} blobs {t2 - t1} s wall; every byte matches")

    settlement = session.close()  # raises SettlementError if value is not conserved
    check(sum(settlement.sp_income.values()) > 0, "SPs earned nothing for serving")
    print(f"settlement: deposited {settlement.total_deposited:.9f} = refunded "
          f"{settlement.total_refunded:.9f} + node income {settlement.total_node_income:.9f}")

    for node in fleet.rpcs:
        st = node.stats
        print(f"{node.rpc_id}: chunksets decoded {st.chunksets_decoded} "
              f"(on host {st.chunksets_decoded_on_host}), cache hits {st.cache_hits}")
        check(st.chunksets_decoded_on_host == 0, f"{node.rpc_id} decoded on the host")
    check(sum(n.stats.chunksets_decoded for n in fleet.rpcs) > 0, "nothing was decoded")
    return ops.gf_traffic()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-replica phase, one RPC node per chip")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not at {SRC}: run from a checkout of the repo")
    sys.path[:0] = [str(SRC), str(SRC.parent)]  # the program; the harness's CompileLog

    import jax
    import numpy as np

    devices = tpu_devices(args.chips)
    from bench.run import CompileLog
    from repro.kernels import ops

    cache_dir = ops.enable_compile_cache()
    compiles = CompileLog()
    print(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}")
    rng = np.random.default_rng(SEED)
    if args.chips == 1:
        kernel_parity(devices[0], rng)
    # one chip: two RPC nodes share it; four chips: node i decodes on chip i
    traffic = serve(2 if args.chips == 1 else args.chips, rng)
    for dev in devices[: args.chips]:
        calls, nbytes = traffic.get(dev, (0, 0))
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"read-phase gf_matmul on {dev}: {calls} calls, {nbytes} bytes of B; "
              f"peak_bytes_in_use {peak}")
        check(calls > 0, f"no kernel call ran on {dev} during the reads")
    print(f"compiles: {compiles.compiles} XLA programs in {compiles.seconds} s "
          f"({compiles.cache_hits} from the persistent cache); "
          f"gf_matmul programs {ops.gf_compilations()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
