"""Nearest-rank 95th percentile of the wall time of every call in the
window, each call ending in ``block_until_ready``, in ms."""

from bench.stats import percentiles


def read(run):
    if not run.durations_s:
        return None
    return percentiles(run.durations_s, (95.0,))[95.0] * 1e3
