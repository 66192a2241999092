"""End-to-end training driver: Shelby storage plane + JAX compute plane.

Builds a simulated Shelby deployment (contract + SPs + RPC), writes the
token corpus into it, then trains with coded checkpointing, hedged data
reads, SP failure injection and restart.  ``--arch`` accepts any assigned
architecture (reduced configs via --smoke for CPU).

  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --steps 40 --fail-at 25
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get, get_smoke
from repro.configs.shelby import CONFIG
from repro.core.contract import ShelbyContract
from repro.core.placement import SPInfo
from repro.data.pipeline import BlobTokenDataset, write_token_corpus
from repro.net.fleet import CacheAffinityPolicy, RPCFleet
from repro.storage.blob import BlobLayout
from repro.storage.checkpoint import CheckpointManager
from repro.storage.repair import RepairCoordinator
from repro.storage.rpc import RPCNode
from repro.storage.sdk import ShelbyClient
from repro.storage.sp import ServiceSpec, StorageProvider
from repro.train.loop import Trainer


def build_cluster(num_sps: int = 8, layout: BlobLayout | None = None,
                  num_rpcs: int = 1):
    """A simulated deployment fronted by the fleet-first client.

    RPC node ``r`` decodes on JAX device ``r`` (round robin when there are
    fewer devices than nodes), through the GF matmul `kernels/ops.py`
    chooses for it: the Pallas kernel on a TPU, numpy on the CPU.
    """
    layout = layout or BlobLayout(k=4, m=2, chunkset_bytes_target=256 * 1024)
    contract = ShelbyContract()
    sps = {}
    for i in range(num_sps):
        contract.register_sp(SPInfo(sp_id=i, stake=1000.0, dc=f"dc{i % 3}", rack=f"r{i % 4}"))
        sps[i] = StorageProvider(
            i, service=ServiceSpec(slots=CONFIG.sp_service_slots)
        )
    devices = jax.devices()
    rpcs = [
        RPCNode(f"rpc{r}", contract, sps, layout, cache_chunksets=32,
                decode_matmul=CONFIG.decode_matmul,
                device=devices[r % len(devices)],
                cache_ttl_ms=CONFIG.rpc_cache_ttl_ms,
                cache_admit_bytes=CONFIG.rpc_cache_admit_bytes,
                admission=CONFIG.admission(),
                single_flight=CONFIG.rpc_single_flight)
        for r in range(num_rpcs)
    ]
    fleet = RPCFleet(rpcs, CacheAffinityPolicy())
    client = ShelbyClient(contract, fleet, deposit=1e9, das=CONFIG.das())
    return contract, sps, fleet.primary, client


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=0,
                    help="crash an SP + restart from coded checkpoint at this step")
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    contract, sps, rpc, client = build_cluster()

    # corpus lives in Shelby; the pipeline is a paying, hedged read client
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, 200_000, dtype=np.int32)
    corpus = write_token_corpus(client, tokens)
    ds = BlobTokenDataset(client, corpus, batch=args.batch, seq_len=args.seq)

    ckpt = CheckpointManager(client, num_host_shards=2)
    repair = RepairCoordinator(contract, sps, rpc.layout)
    trainer = Trainer(cfg, ckpt=ckpt, repair=repair, ckpt_every=args.ckpt_every)
    state = trainer.init_state()

    batches = ds.batches(args.steps * 2, background=False)
    if args.fail_at and args.fail_at < args.steps:
        state, rep1 = trainer.run(state, batches, args.fail_at)
        print(f"[driver] step {args.fail_at}: loss={rep1.final_loss:.4f} — injecting SP failure")
        victim = next(iter(sps))
        sps[victim].crash()
        # restart: restore from coded checkpoint (k-of-n reads absorb the loss)
        restored, step0 = trainer.restore_latest(state)
        if restored is None:
            restored, step0 = state, args.fail_at
        print(f"[driver] restarted from step {step0} with SP {victim} down")
        sps[victim].recover()
        sps[victim].wipe()
        n_rep = len(repair.repair_all())
        print(f"[driver] repaired {n_rep} chunks (MSR where possible)"
              + (f"; {len(repair.failures)} UNRECOVERABLE" if repair.failures else ""))
        state, rep2 = trainer.run(restored, batches, args.steps - step0, start_step=step0)
        losses = rep1.losses + rep2.losses
    else:
        state, rep = trainer.run(state, batches, args.steps)
        losses = rep.losses

    settlement = client.settle()  # broadcast refunds; SPs realize income
    print(f"[driver] done: steps={len(losses)} first={losses[0]:.4f} last={losses[-1]:.4f} "
          f"reads_paid=${settlement.total_node_income:.6f} "
          f"sp_income=${sum(settlement.sp_income.values()):.6f} "
          f"cache_hits={rpc.stats.cache_hits}")
    k = max(len(losses) // 4, 1)  # head/tail means: single steps are noisy
    assert sum(losses[-k:]) / k < sum(losses[:k]) / k, "loss must decrease"
    return losses


if __name__ == "__main__":
    main()
