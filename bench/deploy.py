"""Build one Shelby deployment from a configuration file.

A configuration (``bench/configs/<name>.json``) fixes the erasure layout,
the storage providers, the RPC fleet with its hot caches and the DAS
extension.  :func:`build` turns it into the objects the program serves
with: the contract, the SPs, the fleet and the client.  Nothing here
changes how the program works; the one addition is :class:`KernelLog`,
which wraps the GF matmul each RPC node is handed so that the shapes of
the kernel calls can be counted from the benchmark's side.
"""
from __future__ import annotations

import dataclasses
import itertools


class KernelLog:
    """Records the (M, K, N) of every GF matmul a node sends to its device."""

    def __init__(self):
        self.shapes: list[tuple[int, int, int]] = []

    def wrap(self, matmul):
        if matmul is None:  # the host path: nothing reaches the device
            return None

        def logged(a, b):
            m, k = a.shape
            self.shapes.append((int(m), int(k), int(b.shape[1])))
            return matmul(a, b)

        return logged


@dataclasses.dataclass
class Deployment:
    config: dict
    layout: object
    contract: object
    sps: dict
    fleet: object
    client: object
    kernels: KernelLog
    devices: list

    def crash_to_erase(self, metas, erased: int) -> list[int]:
        """Crash the SPs that :func:`erasing_victims` picks for every chunkset
        of the blobs ``metas``; return their ids."""
        placements = [{ck: meta.placement[(cs, ck)] for ck in range(self.layout.n)}
                      for meta in metas for cs in range(meta.num_chunksets)]
        victims = erasing_victims(placements, self.layout.k, self.layout.m, erased)
        for sp_id in victims:
            self.sps[sp_id].crash()
        return victims

    def crash_data_holders(self, meta, count: int, chunkset: int = 0) -> list[int]:
        """Crash the ``count`` lowest-numbered SPs that hold data chunks of
        ``chunkset`` of the blob ``meta``; return their ids."""
        holders = sorted({meta.placement[(chunkset, ck)] for ck in range(self.layout.k)})
        victims = holders[:count]
        for sp_id in victims:
            self.sps[sp_id].crash()
        return victims


def erasing_victims(placements: list[dict], k: int, m: int, erased: int) -> list[int]:
    """The fewest SPs whose crash leaves every chunkset with at least
    ``erased`` of its data chunks (0..k-1) erased and none with more than
    ``m`` chunks erased, so that each stays readable; among sets of that
    size, the first in the order of SP ids.

    ``placements``: one dict per chunkset, chunk index -> SP id.
    """
    if erased <= 0:
        return []
    sps = sorted({sp for p in placements for sp in p.values()})
    for size in range(erased, m + 1):
        for victims in itertools.combinations(sps, size):
            down = set(victims)
            if all(sum(p[ck] in down for ck in range(k)) >= erased
                   and sum(sp in down for sp in p.values()) <= m for p in placements):
                return list(victims)
    raise ValueError(f"no {m} or fewer SPs erase {erased} data chunks of every chunkset")


def layout_of(config: dict):
    from repro.storage.blob import BlobLayout

    return BlobLayout(k=config["k"], m=config["m"],
                      chunkset_bytes_target=config["chunkset_bytes_target"])


def check_layout(config: dict) -> None:
    """The layout the program derives has the (n, k, alpha, w) the source states."""
    lay = layout_of(config)
    got = {"n": lay.n, "k": lay.k, "alpha": lay.code.alpha, "w": lay.w}
    want = {key: config[key] for key in got}
    if got != want:
        raise ValueError(f"{config['name']}: the program derives {got}, the source states {want}")


def _rpc_node(dep: Deployment, rpc_id: str, device, cache_chunksets: int):
    from repro.kernels import ops
    from repro.net.scheduler import HedgedScheduler
    from repro.storage.rpc import RPCNode

    cfg = dep.config
    matmul = ops.resolve_decode_matmul(cfg["decode_matmul"], device)
    return RPCNode(rpc_id, dep.contract, dep.sps, dep.layout,
                   cache_chunksets=cache_chunksets,
                   scheduler=HedgedScheduler(hedge=cfg["rpc_hedge"]),
                   hedge=cfg["rpc_hedge"],
                   decode_matmul=dep.kernels.wrap(matmul), device=device)


def build(config: dict, devices: list) -> Deployment:
    """Contract, SPs, an RPC fleet (node r on ``devices[r % len]``) and a client."""
    from repro.core.contract import ShelbyContract
    from repro.core.placement import SPInfo
    from repro.net.fleet import RPCFleet, make_policy
    from repro.storage.das import DASSpec
    from repro.storage.sdk import ShelbyClient
    from repro.storage.sp import ServiceSpec, StorageProvider

    check_layout(config)
    contract = ShelbyContract()
    sps = {}
    for i in range(config["num_sps"]):
        contract.register_sp(SPInfo(sp_id=i, stake=1000.0, dc=f"dc{i % config['num_dcs']}",
                                    rack=f"r{i % config['racks_per_dc']}"))
        sps[i] = StorageProvider(i, service=ServiceSpec(slots=config["sp_service_slots"]))
    dep = Deployment(config=config, layout=layout_of(config), contract=contract, sps=sps,
                     fleet=None, client=None, kernels=KernelLog(), devices=list(devices))
    nodes = [_rpc_node(dep, f"rpc{r}", devices[r % len(devices)],
                       config["cache_chunksets_per_node"])
             for r in range(config["rpc_nodes"])]
    dep.fleet = RPCFleet(nodes, make_policy(config["routing_policy"]))
    das = DASSpec(k=config["das_k"], share_bytes=config["das_share_bytes"])
    dep.client = ShelbyClient(contract, dep.fleet, deposit=1e9, das=das)
    return dep
