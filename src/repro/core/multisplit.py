"""The multisplit primitive (paper §3–§5), TPU-native.

Structure follows the paper's parallel model exactly (§4.1):

    {local prescan} -> {one global scan} -> {local postscan + scatter}

* prescan:   per-tile bucket histograms -> the ``m x L`` matrix ``H``.
* scan:      ONE exclusive prefix-sum over the row-vectorized ``H``
             (bucket-major), giving ``G[b, l]`` = #elements in earlier
             buckets anywhere + #elements of bucket ``b`` in earlier tiles.
* postscan:  per-tile local offsets (stable rank within bucket inside the
             tile), final position ``p(i) = G[b, tile] + local_offset``
             (paper eq. (2)); for WMS/BMS the tile is reordered bucket-major
             first (paper §4.7) so the global scatter writes contiguous
             per-bucket runs.

Hardware adaptation (see DESIGN.md §2): the warp-ballot direct solve is
replaced by a one-hot matrix direct solve over a VMEM-resident tile — the
same binary matrix ``H̄`` of paper §4.5, built with vector compares instead
of ``__ballot`` and reduced/scanned with MXU/VPU ops instead of ``__popc``.

Execution is owned by :mod:`repro.core.pipeline` (DESIGN.md §3, §10):
``multisplit`` resolves a :class:`repro.core.pipeline.MultisplitPlan`
through the backend registry and runs it, so the
postscan + reorder is ONE fused evaluation per tile on every backend.

Three variants map to the paper's three implementations:

* ``method="dms"``  — no reorder (Direct Multisplit).
* ``method="wms"``  — tile-local reorder, small tiles (Warp-level MS).
* ``method="bms"``  — tile-local reorder, large tiles (Block-level MS).

Beyond the paper's single flat problem, :func:`batched_multisplit` and
:func:`segmented_multisplit` run MANY independent multisplits (per batch
row / per ragged segment) in one plan launch (DESIGN.md §9).

NOTE (PR-4): :mod:`repro.ops` is the STABLE public facade over this module
— transform-native (``jax.vmap`` dispatches onto the batched plan, the
key-value op is differentiable) and built on hashable
:class:`~repro.core.identifiers.BucketSpec` values.  New consumers should
import ``repro.ops``; this module remains the execution layer.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.identifiers import BucketSpec
from repro.core.pipeline import (        # re-exported for consumers/tests
    BMS_TILE,
    MultisplitResult,
    WMS_TILE,
    backend_for,
    global_scan,
    make_batched_plan,
    make_plan,
    make_segmented_plan,
    segment_ids_from_starts,
    tile_local_offsets,
)

Array = jnp.ndarray

__all__ = [
    "WMS_TILE", "BMS_TILE", "MultisplitResult", "global_scan",
    "tile_local_offsets", "multisplit_ref", "multisplit",
    "batched_multisplit", "segmented_multisplit", "segment_ids_from_starts",
]


# ---------------------------------------------------------------------------
# Reference oracle: paper eq. (1), single subproblem == whole input
# ---------------------------------------------------------------------------

def multisplit_ref(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
) -> MultisplitResult:
    """O(n·m) direct evaluation of eq. (1). Oracle for everything else."""
    from repro.core.pipeline import direct_solve_reference

    return direct_solve_reference(keys, bucket_fn, values)


# ---------------------------------------------------------------------------
# The multisplit entry point: resolve a plan, run it
# ---------------------------------------------------------------------------

def multisplit(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
    tile: Optional[int] = None,
    backend: Optional[str] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
) -> MultisplitResult:
    """Stable multisplit of ``keys`` (and optional ``values``) into buckets.

    ``method``: "dms" (no tile reorder), "wms" (reorder, small tiles),
    "bms" (reorder, large tiles). All three produce identical output
    (paper §4.7: the reorder changes data movement, not the result); they
    differ in the width L of the global scan and in scatter contiguity.

    ``backend``: "reference", "vmap", "pallas-interpret", or "pallas" —
    registered in :mod:`repro.core.pipeline.registry`; ``None`` takes
    :func:`~repro.core.pipeline.default_backend` (the compiled ``pallas``
    kernels on a TPU for 32-bit keys, else ``vmap``).

    ``mode`` selects a partial pipeline (DESIGN.md §10): ``counts_only``
    (prescan + reduce — the §7.3 histogram; only starts/counts are
    computed) or ``positions_only`` (the eq. (2) permutation without
    materializing reordered keys). Both are key-only.

    ``family`` pins the kernel family of the local solve (``"onehot"`` /
    ``"packed"``, DESIGN.md §12); ``None`` auto-resolves it per shape.
    Families are bitwise identical — the knob changes cost, not results.
    """
    plan = make_plan(
        keys.shape[0],
        bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=backend_for(backend, keys.shape[0], keys.dtype),
        tile=tile,
        bucket_fn=bucket_fn,
        mode=mode,
        family=family,
    )
    return plan(keys, values)


# ---------------------------------------------------------------------------
# Batched / segmented entry points (DESIGN.md §9): many independent
# multisplits in ONE plan launch instead of a host loop over subproblems.
# ---------------------------------------------------------------------------

def batched_multisplit(
    keys: Array,
    bucket_fn: BucketSpec,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
    tile: Optional[int] = None,
    backend: Optional[str] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
) -> MultisplitResult:
    """Multisplit every row of ``keys`` (b, n) independently in one launch.

    Bitwise identical to calling :func:`multisplit` on each row: returns
    (b, n) keys/values/permutation and (b, m) per-row starts/counts.
    ``mode`` selects a partial pipeline as in :func:`multisplit`.
    """
    if keys.ndim != 2:
        raise ValueError(f"batched_multisplit expects (b, n) keys, got {keys.shape}")
    b, n = keys.shape
    plan = make_batched_plan(
        b, n, bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=backend_for(backend, n, keys.dtype),
        tile=tile,
        bucket_fn=bucket_fn,
        mode=mode,
        family=family,
    )
    return plan(keys, values)


def _empty_segmented_result(
    keys: Array, values: Optional[Array], m: int, mode: str
) -> MultisplitResult:
    """The s == 0 (zero-request step) result: (0, m) counts/starts and empty
    data arrays, consistent with the s >= 1 shapes. A continuous-batching
    step with no admitted requests hits this constantly (ISSUE 9 S1); it
    used to be a ValueError from the plan layout validator."""
    if keys.shape[0] != 0:
        raise ValueError(
            f"segment_starts is empty but keys has {keys.shape[0]} elements; "
            f"0 segments can only own 0 keys"
        )
    zeros = jnp.zeros((0, m), jnp.int32)
    perm = jnp.zeros((0,), jnp.int32)
    if mode == "counts_only":
        return MultisplitResult(None, None, zeros, zeros, None)
    if mode == "positions_only":
        return MultisplitResult(None, None, zeros, zeros, perm)
    return MultisplitResult(keys, values, zeros, zeros, perm)


def segmented_multisplit(
    keys: Array,
    bucket_fn: BucketSpec,
    segment_starts,
    values: Optional[Array] = None,
    *,
    method: str = "bms",
    tile: Optional[int] = None,
    backend: Optional[str] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
) -> MultisplitResult:
    """Multisplit every ragged segment of flat ``keys`` independently in one
    launch. ``segment_starts`` is an (s,) ascending vector of start offsets
    with ``segment_starts[0] == 0``; segment i spans
    ``[segment_starts[i], segment_starts[i+1])`` (the last ends at n) and
    empty segments are allowed.

    Bitwise identical to slicing out each segment and calling
    :func:`multisplit` on it: each segment keeps its input span in the
    output, ``bucket_starts``/``bucket_counts`` are (s, m) segment-local,
    and ``permutation`` is segment-local. ``mode`` selects a partial
    pipeline as in :func:`multisplit`.

    ``s == 0`` (no segments at all — a zero-request serving step) is legal
    with empty ``keys`` and returns (0, m) counts/starts and empty data
    arrays (the :mod:`repro.ops` facade short-circuits identically).
    """
    seg = jnp.asarray(segment_starts, jnp.int32)
    if seg.shape[0] == 0:
        return _empty_segmented_result(
            keys, values, bucket_fn.num_buckets, mode
        )
    plan = make_segmented_plan(
        keys.shape[0], int(seg.shape[0]), bucket_fn.num_buckets,
        method=method,
        key_value=values is not None,
        backend=backend_for(backend, keys.shape[0], keys.dtype),
        tile=tile,
        bucket_fn=bucket_fn,
        mode=mode,
        family=family,
    )
    return plan(keys, values, segment_starts=seg)
