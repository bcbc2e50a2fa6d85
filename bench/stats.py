"""Order statistics the benchmark reports, kept here so that a change to the
program cannot move them."""

from __future__ import annotations

import math
from typing import Dict, Iterable, Sequence


def percentiles(samples: Iterable[float],
                ps: Sequence[float] = (50.0, 95.0)) -> Dict[float, float]:
    """Exact nearest-rank percentiles, with no interpolation: percentile ``p``
    of ``n`` sorted samples is element ``ceil(p/100 * n) - 1`` (0-indexed),
    the smallest sample that is at least ``p`` percent of the data, so a tail
    is always a time that was observed. Empty input gives NaN."""
    xs = sorted(float(x) for x in samples)
    out: Dict[float, float] = {}
    for p in ps:
        if not 0.0 <= p <= 100.0:
            raise ValueError(f"percentile must be in [0, 100], got {p}")
        if not xs:
            out[p] = float("nan")
            continue
        out[p] = xs[max(1, math.ceil(p / 100.0 * len(xs))) - 1]
    return out
