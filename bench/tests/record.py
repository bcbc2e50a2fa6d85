"""Record a profiler trace of a few calls of a cell, small, for the tests.

    python -m bench.tests.record <out.xplane.pb.gz> [--workload ms_kv_m256] \\
        [--n 16384] [--calls 3] [--seed 3000000011]

Run on the chip. After one warm-up call, ``--calls`` calls run under the
profiler as the harness runs them, each inside the benchmark's spans
(``bench.call`` around ``bench.dispatch`` and ``bench.sync``), and the
trace is written gzipped to the path given."""

from __future__ import annotations

import argparse
import glob
import gzip
import os
import sys
import tempfile

import jax

from bench import harness, traffic
from bench import trace as trace_mod
from bench.tests.cases import small_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--workload", default="ms_kv_m256")
    ap.add_argument("--n", type=int, default=1 << 14)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=3000000011)
    args = ap.parse_args(argv)
    cell = small_cell(args.workload, args.n)
    devices = jax.devices()[:cell.chips]
    mesh, sharding = harness._placement(devices)
    key_sets, values = traffic.make_inputs(cell.traffic, cell.cfg, args.seed, sharding)
    fn = cell.driver.build(cell.cfg, mesh)
    jax.block_until_ready(fn(key_sets[0], values))
    log_dir = tempfile.mkdtemp(prefix="bench_record_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for i in range(args.calls):
            with jax.profiler.TraceAnnotation(trace_mod.SPAN_CALL):
                with jax.profiler.TraceAnnotation(trace_mod.SPAN_DISPATCH):
                    result = fn(key_sets[i % len(key_sets)], values)
                with jax.profiler.TraceAnnotation(trace_mod.SPAN_SYNC):
                    jax.block_until_ready(result)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    with open(path, "rb") as f, gzip.open(args.out, "wb", compresslevel=9) as g:
        g.write(f.read())
    print(f"{args.out}: {os.path.getsize(args.out)} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
