"""Run one cell of the benchmark once, on the chips it asks for.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of ``BENCHMARK.json``'s
``workloads``. With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler trace
of the window. The last line of standard output is one JSON object; a run
that finds no TPU, fewer chips than the cell asks for, ``REPRO_INTERPRET``
set or a device kind missing from ``bench/peaks.json`` exits non-zero and
prints none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of deleting it")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import jax

        from bench import harness
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: cannot import the program or the harness: {e}", file=sys.stderr)
        return 2

    prefix = ["bench"]

    def log(msg: str) -> None:
        print(f"[{' '.join(prefix)}] {msg}", flush=True)

    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
            peaks = json.load(f)
        cell = harness.resolve_cell(bench, args.workload)
        devices, peak = harness.require_chips(cell.chips, peaks)
    except (OSError, ValueError, harness.BenchError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    prefix += [devices[0].platform, repr(devices[0].device_kind),
               f"count={len(jax.devices())}"]
    enable_compile_cache()
    # Cache every program, the small ones of an eager call included, so that
    # only a checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"workload={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} chips={cell.chips} jax={jax.__version__}")
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, peak=peak, t_start=T_START, log=log,
        trace_dir=args.trace_dir)
    for name, m in result["metrics"].items():
        log(f"metric {name}={m['value']!r} {m['unit']}")
    compared = " ".join(f"{k}={c['value']} (limit {c['limit']})"
                        for k, c in result["compared"].items())
    print(f"[{' '.join(prefix)}] correct={result['correct']} compared: {compared}",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
