"""Device-wide histogram built from the multisplit prescan (paper §7.3).

The paper reuses the pre-scan stage (tile histograms) and sums across
subproblems instead of scanning — on TPU the "atomic add into the global
array" becomes a tree reduction over the per-tile histogram matrix (no
atomics; DESIGN.md §2). This is exactly a ``counts_only`` partial pipeline
(DESIGN.md §10): {prescan, reduce}, no scan, no scatter — so ``histogram``
is a thin wrapper over one :func:`repro.core.pipeline.make_plan` call. Tile
sizes come from the shared heuristic/autotune cache (the old hardcoded
per-module tile constant and private plan-layer reach are gone).
``histogram_even`` / ``histogram_range`` mirror CUB's HistogramEven /
HistogramRange used as the paper's comparison.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.identifiers import BucketSpec, even_buckets, range_buckets
from repro.core.pipeline import backend_for, make_plan

Array = jnp.ndarray


def histogram(
    keys: Array,
    bucket_fn: BucketSpec,
    *,
    tile: Optional[int] = None,
    backend: Optional[str] = None,
) -> Array:
    """Global bucket counts: a ``counts_only`` pipeline (prescan + reduce).

    ``tile=None`` resolves through the shared per-shape heuristic/autotune
    cache — the same tile every other consumer of this shape gets;
    ``backend=None`` takes :func:`~repro.core.pipeline.default_backend`.
    """
    plan = make_plan(
        keys.shape[0],
        bucket_fn.num_buckets,
        method="bms",
        backend=backend_for(backend, keys.shape[0], keys.dtype),
        tile=tile,
        bucket_fn=bucket_fn,
        mode="counts_only",
    )
    return plan(keys).bucket_counts


def histogram_even(
    keys: Array, lo: float, hi: float, num_buckets: int, **kw
) -> Array:
    """Evenly spaced bins (paper §7.3 scenario 1)."""
    return histogram(keys, even_buckets(lo, hi, num_buckets), **kw)


def histogram_range(keys: Array, splitters: Array, **kw) -> Array:
    """Arbitrary splitter bins via binary search (paper §7.3 scenario 2)."""
    return histogram(keys, range_buckets(splitters), **kw)
