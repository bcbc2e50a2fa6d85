"""Runs of a cell with its timed path as it is, with its control in its
place, or with a fault planted under it: the harness's look for a chip is
skipped, the rest of a run is not.

    python -m bench.tests.cases <workload> --cases program control ... \\
        [--seeds 1 2 3] [--n 4096] [--seconds 0.2] [--config <name> --chips <n>]

prints one JSON line per (case, seed) with the run's ``correct`` and every
number it compared; ``--n 0`` keeps the configuration's own size. The tests
run it at a small size on the CPU; on the chip, at the cell's own size, it
gives the readings that the limits are set from."""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from bench import harness
from bench.tests import faults

SMALL_N = 1 << 12
PEAK = {"hbm_bytes_per_s": 819e9}


def small_cell(workload: str, n: int = SMALL_N, config: str = None,
               chips: int = 1) -> harness.Cell:
    """The cell ``workload``; where ``BENCHMARK.json`` does not list it yet,
    the cell of ``config`` on ``chips`` chips under the uniform traffic."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    if config is not None and all(w["name"] != workload for w in bench["workloads"]):
        bench["configs"].append({"name": config, "reduced": [],
                                 "file": f"bench/configs/{config}.json"})
        bench["workloads"].append({"name": workload, "config": config,
                                   "traffic": "uniform", "chips": chips})
    cell = harness.resolve_cell(bench, workload)
    if n:
        cell.cfg["n"] = n
    return cell


def broken_call(cell: harness.Cell, case: str, mesh):
    """The callable that takes the timed path's place, or ``None``."""
    if case == "program":
        return None
    if case == "control":
        return cell.driver.control(cell.cfg, mesh)
    program = cell.driver.build(cell.cfg, mesh)
    return faults.SHARDED_FAULTS[case](program, cell.cfg, mesh)


def run_case(cell: harness.Cell, case: str, seed: int = 2**31 + 11,
             seconds: float = 0.2, call=None, **kw) -> dict:
    devices = jax.devices()[:cell.chips]
    if call is None:
        call = broken_call(cell, case, harness.make_mesh(devices))
    return harness.run_cell(cell, seed=seed, seconds=seconds, devices=devices,
                            peak=PEAK, t_start=time.perf_counter(),
                            log=lambda msg: None, call=call, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--cases", nargs="+", default=["program"])
    ap.add_argument("--seeds", nargs="+", type=int, default=[2**31 + 11])
    ap.add_argument("--n", type=int, default=SMALL_N)
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--config", default=None,
                    help="run a cell that BENCHMARK.json does not list yet")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    cell = small_cell(args.workload, args.n, args.config, args.chips)
    mesh = harness.make_mesh(jax.devices()[:cell.chips])
    for case in args.cases:
        call = broken_call(cell, case, mesh)
        for seed in args.seeds:
            out = run_case(cell, case, seed, args.seconds, call=call, trace=False)
            print(json.dumps({"case": case, "seed": seed, "correct": out["correct"],
                              "attempted": out["attempted"],
                              "compared": {k: c["value"]
                                           for k, c in out["compared"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
