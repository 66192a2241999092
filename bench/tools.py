#!/usr/bin/env python3
"""Runs of the harness that the benchmark's own runs never make.

    python3 bench/tools.py control --workload <cell> --fault <name|none> --seeds 1 2 3 --seconds 30

``control`` runs the cell once per seed in one process, with a fault from
``bench/faults.py`` planted under the timed path (``none`` plants nothing;
``control`` plants the one the cell's mix names as its control),
and prints every number the check compared: the readings that a limit is
set from.  It needs the chip, as a run does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import faults, run  # noqa: E402


def _start(workload: str):
    spec = run.prepare(ROOT, workload)
    devices = run.find_devices(spec["cell"]["chips"])
    from repro.kernels import ops

    ops.enable_compile_cache()
    return spec, devices, run.CompileLog()


def control(args) -> int:
    spec, devices, compiles = _start(args.workload)
    fault = {"none": None, "control": spec["mix"]["control"]}.get(args.fault, args.fault)
    for seed in args.seeds:
        with faults.planted(fault):
            result = run.run_cell(spec, devices, seed, args.seconds, False, compiles,
                                  t_start=time.perf_counter())
        print(json.dumps({"workload": args.workload, "fault": args.fault, "seed": seed,
                          "correct": result["correct"], "attempted": result["attempted"],
                          "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                          "work": result["work"],
                          "checks": {k: v["value"] for k, v in result["checks"].items()}}),
              flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--fault", required=True, choices=sorted(faults.FAULTS) + ["none", "control"])
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        return control(args)
    except run.Failure as e:
        print(f"tools: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
