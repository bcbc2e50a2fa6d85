"""Benchmark timing utilities."""

import time

import jax
import numpy as np

# The shared exact (interpolation-free, nearest-rank) percentile estimator:
# one implementation for serving metrics and the SLO bench, so a reported
# p99 is an OBSERVED sample, never an interpolated value that no request
# actually experienced.  Defined in repro.serving.metrics (benchmarks depend
# on repro, never the reverse) and re-exported here for benchmark code.
from repro.serving.metrics import percentiles  # noqa: E402,F401


def bench(fn, *args, warmup=1, trials=3):
    """Median wall time (s) of a jax function (block_until_ready)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def row(name, seconds, derived=""):
    print(f"{name},{seconds * 1e6:.1f},{derived}")
