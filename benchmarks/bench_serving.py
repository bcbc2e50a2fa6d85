"""Serving benchmark: sustained QPS at a p99 latency SLO (DESIGN.md §16).

    PYTHONPATH=src:. python benchmarks/bench_serving.py --quick --ci-floor 0.9

Three measurements over the same synthetic request set:

1. **Offline oracle** — a perfect scheduler's throughput lower bound: the
   whole request set greedily packed into batches offline (tighter of
   length-sorted and FIFO token-fill), then every batch launched
   back-to-back through the SAME padded step function the server uses.
   Batch assembly is inside the timed region (the server pays it too), so
   the ratio below compares schedulers, not accounting tricks.
2. **Closed-loop ratio** — the real queue + admission + metrics path in
   the saturation regime, divided by the oracle. ``--ci-floor R`` makes
   this a gate: the continuous-batching machinery may cost at most
   ``(1-R)`` of the perfect scheduler's throughput.
3. **Open-loop SLO probe** — Poisson arrivals at ~70% of oracle capacity
   (or ``--qps``): exact nearest-rank p50/p95/p99 latency, sustained QPS,
   shed count, and PASS/FAIL against ``--slo-ms``.

Every run conservation-checks request accounting (``dropped_by_bug == 0``).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from benchmarks.common import row
from repro.serving import (
    ServerLoop, ServingConfig, closed_loop, open_loop, poisson_arrivals,
    synthetic_requests,
)
from repro.serving.request import Request

QUICK_REQUESTS = 10_000
FULL_REQUESTS = 40_000
OPEN_LOOP_LOAD = 0.7      # offered rate as a fraction of oracle capacity
TRIALS = 3                # paired (oracle, closed) trials; ratio = best pair


def _bench_config(quick: bool) -> ServingConfig:
    return ServingConfig(
        num_experts=8,
        capacity=64,
        max_batch_requests=512,
        max_batch_tokens=4096,
        max_wait=0.005,
        max_queue_depth=FULL_REQUESTS + 16,   # closed loop holds the full set
    )


def _greedy_pack(cfg: ServingConfig, reqs: List[np.ndarray],
                 order: List[int]) -> List[List[Request]]:
    batches: List[List[Request]] = []
    cur: List[Request] = []
    tokens = 0
    for i in order:
        r = Request(i, reqs[i], 0.0)
        if cur and (len(cur) >= cfg.max_batch_requests
                    or tokens + r.length > cfg.max_batch_tokens):
            batches.append(cur)
            cur, tokens = [], 0
        cur.append(r)
        tokens += r.length
    if cur:
        batches.append(cur)
    return batches


def offline_oracle(cfg: ServingConfig, reqs: List[np.ndarray]) -> Tuple[float, float]:
    """(wall_s, qps) of the perfect scheduler: the whole request set packed
    offline (the TIGHTER of length-sorted and FIFO token-fill greedy
    packings — sorted groups similar lengths, FIFO fills the token budget
    densely when the request cap would otherwise bind), no queue, no
    deadline, no metrics — just pack + launch."""
    loop = ServerLoop(cfg)            # borrowed for _pack/_jit_step only
    loop.prewarm()
    n = len(reqs)
    batches = min(
        _greedy_pack(cfg, reqs, sorted(range(n), key=lambda i: len(reqs[i]))),
        _greedy_pack(cfg, reqs, list(range(n))),
        key=len,
    )
    t0 = time.monotonic()
    out = None
    for b in batches:                 # assembly INSIDE the timed region
        ids, starts, _ = loop._pack(b)
        out = loop._jit_step(ids, starts)   # async, like the pipelined server
    jax.block_until_ready(out)
    wall = time.monotonic() - t0
    return wall, len(reqs) / wall


def run_serving_slo(
    requests: int = QUICK_REQUESTS,
    *,
    quick: bool = True,
    qps: float | None = None,
    slo_ms: float = 200.0,
    ci_floor: float | None = None,
    seed: int = 0,
    fault_rate: float = 0.0,
    verify: int | None = None,
) -> Dict[str, float]:
    """The full serving benchmark; returns the combined results dict and
    raises SystemExit(1) when a gate (--ci-floor / conservation /
    chaos verify_mismatches) fails.  ``fault_rate > 0`` arms seeded
    dispatch-level fault injection (the CI chaos-smoke mode, DESIGN.md
    §17); ``verify`` arms runtime output verification for the run."""
    from repro.runtime import FaultInjector, resilience as _rz

    _rz.reset_stats()
    if verify is not None:
        _rz.set_verify(verify)
    if fault_rate > 0.0:
        _rz.set_fault_injector(
            FaultInjector(dispatch_rate=fault_rate, seed=seed))

    def _disarm() -> None:
        _rz.set_fault_injector(None)
        if verify is not None:
            _rz.set_verify(None)

    cfg = _bench_config(quick)
    reqs = synthetic_requests(requests, cfg.num_experts, seed=seed)

    # 1+2. oracle vs closed loop, in PAIRED trials: each trial measures both
    # schedulers back-to-back under the same machine conditions and the
    # ratio is the best paired ratio — wall-clock noise on a shared host
    # hits both sides of a pair, so the pairing is what makes a CI floor on
    # the ratio meaningful.
    oracle_qps = closed_qps = ratio = 0.0
    oracle_wall, s_closed = None, None
    for _ in range(TRIALS):
        o_wall, o_qps = offline_oracle(cfg, reqs)
        loop = ServerLoop(cfg)       # fresh queue/metrics; jit cache shared
        loop.prewarm()
        s = closed_loop(loop, reqs)
        if s["dropped_by_bug"] != 0:
            print(f"FAIL: closed loop dropped requests: {s}", file=sys.stderr)
            _disarm()
            raise SystemExit(1)
        c_qps = requests / s["wall_s"]
        if c_qps / o_qps > ratio:
            ratio = c_qps / o_qps
            oracle_wall, oracle_qps = o_wall, o_qps
            s_closed, closed_qps = s, c_qps
    row("serving_oracle", oracle_wall / requests, f"qps={oracle_qps:.0f}")
    row("serving_closed", s_closed["wall_s"] / requests,
        f"qps={closed_qps:.0f} oracle_ratio={ratio:.3f}")

    # 3. open-loop Poisson SLO probe
    offered = qps if qps is not None else OPEN_LOOP_LOAD * oracle_qps
    loop2 = ServerLoop(cfg)
    loop2.prewarm()
    arrivals = poisson_arrivals(requests, offered, seed=seed)
    s_open = open_loop(loop2, reqs, arrivals)
    if s_open["dropped_by_bug"] != 0:
        print(f"FAIL: open loop dropped requests: {s_open}", file=sys.stderr)
        _disarm()
        raise SystemExit(1)
    slo_ok = s_open["latency_p99_ms"] <= slo_ms
    row("serving_open_p99", s_open["latency_p99_ms"] / 1e6,
        f"offered={offered:.0f} sustained={s_open['qps_sustained']:.0f} "
        f"slo={'PASS' if slo_ok else 'FAIL'}")

    degradations = int(s_closed["degradations"] + s_open["degradations"])
    mismatches = int(s_closed["verify_mismatches"] + s_open["verify_mismatches"])
    results = {
        "requests": requests,
        "oracle_qps": oracle_qps,
        "closed_qps": closed_qps,
        "oracle_ratio": ratio,
        "offered_qps": offered,
        "slo_ms": slo_ms,
        "slo_pass": bool(slo_ok),
        "fault_rate": fault_rate,
        "degradations": degradations,
        "verify_mismatches": mismatches,
        "open": s_open,
        "closed": {k: s_closed[k] for k in
                   ("completed", "shed", "failed", "retries", "steps",
                    "degradations", "verify_mismatches",
                    "batch_token_occupancy", "batch_requests_mean")},
    }
    # the machine-parsable line the CI step-summary table is built from
    print(f"SERVING_SUMMARY requests={requests} qps={s_open['qps_sustained']:.0f} "
          f"p50_ms={s_open['latency_p50_ms']:.2f} "
          f"p99_ms={s_open['latency_p99_ms']:.2f} "
          f"shed={int(s_open['shed'])} failed={int(s_open['failed'])} "
          f"degradations={degradations} verify_mismatches={mismatches} "
          f"oracle_ratio={ratio:.3f} slo={'PASS' if slo_ok else 'FAIL'}")
    if fault_rate > 0.0:
        # the chaos-smoke markdown step summary is built from these lines
        for e in _rz.events():
            fields = " ".join(f"{k}={v}" for k, v in e.items() if k != "kind")
            print(f"DEGRADATION_EVENT kind={e['kind']} {fields}")

    _disarm()

    if ci_floor is not None and ratio < ci_floor:
        print(f"FAIL: closed-loop/oracle ratio {ratio:.3f} < floor {ci_floor}",
              file=sys.stderr)
        raise SystemExit(1)
    if mismatches > 0:
        # chaos gate: injected DISPATCH faults must degrade, never corrupt —
        # any verified output mismatch is a real bug, not an injected one
        print(f"FAIL: {mismatches} runtime-verification mismatches",
              file=sys.stderr)
        raise SystemExit(1)
    return results


def main(quick: bool = False, argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=quick)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--qps", type=float, default=None,
                    help="open-loop offered rate (default: 0.7 x oracle)")
    ap.add_argument("--slo-ms", type=float, default=200.0)
    ap.add_argument("--ci-floor", type=float, default=None,
                    help="minimum closed-loop/oracle throughput ratio")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="seeded dispatch-fault injection rate (chaos smoke)")
    ap.add_argument("--verify", type=int, default=None, choices=(0, 1, 2),
                    help="runtime verification level for this run")
    args = ap.parse_args(argv)
    n = args.requests or (QUICK_REQUESTS if args.quick else FULL_REQUESTS)
    run_serving_slo(n, quick=args.quick, qps=args.qps, slo_ms=args.slo_ms,
                    ci_floor=args.ci_floor, seed=args.seed,
                    fault_rate=args.fault_rate, verify=args.verify)


if __name__ == "__main__":
    main()
