"""Tile sizing + kernel-family selection: per-shape heuristics and a small
autotune cache (paper Table 1).

The tile height is the paper's subproblem-size knob: larger subproblems
narrow the global scan matrix H but deepen the local solve. Since the
packed-counter family (DESIGN.md §12) the local solve has a second knob —
the KERNEL FAMILY:

* ``"onehot"`` — the dense T×m one-hot/cumsum direct solve (DESIGN.md §2);
  per-key work and VMEM linear in the bucket count.
* ``"packed"`` — bit-packed subword counters with two-level (subtile→tile)
  ranking (paper §4.3); per-key work ~flat in the bucket count.

One module owns the heuristics, the caches, and the timing-based autotuner
so EVERY consumer — flat, batched, segmented plans and the chained radix
pipeline — resolves (tile, family) through the same door.  Family decisions
are memoized WITH the reason they were made (:func:`family_decision`), so a
surprising plan can always be interrogated.

Since the self-tuning layer (DESIGN.md §14,
:mod:`repro.core.pipeline.autotune`) a cache MISS can resolve through
measurement instead of the heuristic: when autotuning is opted in
(``repro.ops.set_autotune(True)`` / ``REPRO_AUTOTUNE=1``), the miss first
consults a persistent on-disk cache keyed by (host fingerprint, backend,
shape class) and otherwise runs the joint timing search, pinning AND
persisting the winner.  The heuristics remain the default.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.identifiers import BucketSpec
from repro.kernels.common import pad_lanes as _pad_lanes

# "warp" tiles vs "block" tiles (paper Table 1 sizing knob).
WMS_TILE = 1024
BMS_TILE = 4096

# VMEM budget for the heuristic (working set of the fused postscan): the
# default scoped-VMEM limit of a v5e TensorCore. Kernels are compiled with
# twice this (kernels/multisplit_tile.py ``VMEM_LIMIT_BYTES``), so a tile the
# cost models admit always fits what Mosaic allocates.
_VMEM_BUDGET_BYTES = 16 << 20
_MIN_TILE = 256

# Kernel families (DESIGN.md §12). The family heuristic switches to packed
# counters once the bucket axis is wide enough that the dense one-hot
# dominates the tile working set. The flip point is the MEASURED host-bench
# crossover (bench_multisplit.py packed_vs_onehot sweep re-run at
# n ∈ {2^18, 2^20}, key-value flat multisplit): packed already wins at m=8
# (1.12–1.25×) and only ties at m=4 — the original 64 was a working-set
# argument that left the whole 8 ≤ m < 64 band on the slower family.
FAMILIES = ("onehot", "packed")
PACKED_MIN_BUCKETS = 8

# digits=1: (n, m_eff, method, key_value, backend);
# digits=2: (n, m_eff, method, key_value, backend, 2, stage_m) — stage_m IS
# part of the fused-pair footprint (_fused2_cost_bytes depends on it), so
# two pair schedules with equal combined m but different digit_split must
# not share a tile entry (regression-tested).
_TILE_CACHE: Dict[Tuple, int] = {}
# digits=1: (n, m_eff, method, backend); digits=2 appends the digits slot —
# fused-pair stage solves are stage_m-wide, and their decisions must never
# collide with genuine digits=1 plans of m == stage_m (regression-tested).
# Values are (family, reason): reasons are recorded so autotune/heuristic
# choices stay explainable after the fact.
_FAMILY_CACHE: Dict[Tuple, Tuple[str, str]] = {}
# (n, m_eff, method, key_value, backend, stage_m) -> in-tile sub-digit stage
# width of the fused2 LSD sweep. ONLY the autotuner writes here; on a miss
# the measured global default (_FUSED2_SUB_BITS) applies.
_SUB_BITS_CACHE: Dict[Tuple, int] = {}


def _family_key(n: int, m: int, method: str, backend: str, digits: int) -> Tuple:
    base = (n, m, method, backend)
    return base if digits == 1 else base + (digits,)


def _tile_key(n: int, m: int, method: str, key_value: bool, backend: str,
              digits: int, stage_m: Optional[int]) -> Tuple:
    base = (n, m, method, key_value, backend)
    if digits == 1:
        return base
    return base + (digits, stage_m or max(1, int(m ** 0.5)))


def _kernel_cost_bytes(t: int, m: int) -> int:
    """Scoped VMEM Mosaic allocates for the compiled fused postscan+reorder
    kernel of either family, in bytes: an upper bound fitted to the v5e
    compiler's own figures ("Scoped allocation with size ..." at a 1 MiB
    limit), e.g. 5.7 MiB at T=512 and 19.4 MiB at T=1024 (dense, m ≤ 256),
    7.1 MiB at T=512 with m_eff=528 (packed). The T×T planes (triangular
    scan, permutation matrix, their iota/compare temporaries) cost ~4.5 f32
    words per T² entry; the T×m̄ one-hot planes ~2 words per entry."""
    return 18 * t * t + 8 * t * _pad_lanes(m) + 2560 * t


def _family_cost_bytes(t: int, m: int, family: str,
                       oblivious: bool = False) -> int:
    """Per-tile working set of the fused postscan kernel, in bytes.

    onehot: one-hot + its cumsum (2·T·m̄ f32) + the triangular-scan and
    permutation matrices (2·T² f32) + ~8 T-vectors. The pre-PR-5 model
    under-counted this (it charged one T·m̄ plane and no cumsum output),
    which is why large-m tiles blew past the budget in practice.  (The
    dense body was always gather-free, so its model has no oblivious term.)

    packed: the (T, ⌈m/k⌉) packed contribution + inclusive-scan planes, the
    small S×m level-2 scan, and ~8 T-vectors — near-flat in m.
    ``oblivious=True`` (kernel backends, DESIGN.md §15) charges what the
    compiler allocates for the compiled kernel of either family
    (:func:`_kernel_cost_bytes`): its T×T planes dominate, so both
    families get the same tile there, while the vmap gather form keeps its
    near-flat profile.
    """
    if oblivious:
        return _kernel_cost_bytes(t, m)
    if family == "packed":
        from repro.kernels.common import packed_layout

        lay = packed_layout(t, m)
        return 4 * (2 * t * lay.w + 3 * lay.n_sub * m + 8 * t)
    m_pad = _pad_lanes(m)
    return 4 * (2 * t * m_pad + 2 * t * t + 8 * t)


def _fused2_cost_bytes(t: int, m: int, stage_m: int, family: str,
                       key_value: bool, oblivious: bool = False) -> int:
    """Per-tile working set of the fused TWO-digit postscan (DESIGN.md §13):
    the double-resident tile model of
    :func:`repro.kernels.common.fused2_vmem_bytes` — the sub-digit LSD
    sweep's reused stage plane plus the ``m``-wide combined pair rows
    (+ the oblivious permutation/pick planes on kernel backends, §15)."""
    from repro.kernels.common import fused2_vmem_bytes

    return fused2_vmem_bytes(
        t, stage_m, family=family, key_value=key_value,
        m_hi=max(1, m // stage_m), oblivious=oblivious,
    )


def _heuristic_tile(
    n: int, m: int, method: str, backend: str, family: str = "onehot",
    digits: int = 1, stage_m: Optional[int] = None, key_value: bool = False,
) -> int:
    from repro.core.pipeline.registry import get_backend

    base = WMS_TILE if method in ("dms", "wms") else BMS_TILE
    tile = base
    # kernel backends trace the oblivious bodies (DESIGN.md §15), so only
    # they carry the oblivious VMEM terms; vmap keeps the gather profile
    obl = get_backend(backend).uses_kernels
    if digits == 2:
        cost = lambda t: _fused2_cost_bytes(
            t, m, stage_m or max(1, int(m ** 0.5)), family, key_value,
            oblivious=obl,
        )
        # A fused pair's global-scan traffic is L·m² words (L = tile count),
        # so pairs only profit when L is SMALL — grow the tile toward the
        # VMEM budget (the sub-digit LSD working set is ~linear in T with a
        # small constant) instead of shrinking from the single-digit base.
        while tile * 2 <= max(n, base) and cost(tile * 2) <= _VMEM_BUDGET_BYTES:
            tile *= 2
        while tile > _MIN_TILE and cost(tile) > _VMEM_BUDGET_BYTES:
            tile //= 2
    else:
        cost = lambda t: _family_cost_bytes(t, m, family, oblivious=obl)
        if obl:
            while tile > _MIN_TILE and cost(tile) > _VMEM_BUDGET_BYTES:
                tile //= 2
    if n < tile:
        # tiny input: one tile, padded to the next power of two (>= 128 lanes)
        tile = max(128, 1 << max(n - 1, 0).bit_length())
    return tile


def _heuristic_family(n: int, m: int, method: str, backend: str) -> Tuple[str, str]:
    from repro.core.pipeline.registry import get_backend

    be = get_backend(backend)
    if not be.tiled:
        return "onehot", "untiled direct-solve backend: no tile local solve"
    if "packed" not in be.families:
        return "onehot", f"backend {backend!r} advertises no packed support"
    if m >= PACKED_MIN_BUCKETS:
        return "packed", (
            f"m_eff={m} >= {PACKED_MIN_BUCKETS}: packed subword counters keep "
            f"the local solve ~flat in the bucket count (DESIGN.md §12)"
        )
    return "onehot", (
        f"m_eff={m} < {PACKED_MIN_BUCKETS}: the dense one-hot local solve is "
        f"cheaper at narrow bucket axes"
    )


def resolve_kernel_family(
    n: int, m: int, method: str, backend: str, requested: Optional[str] = None,
    digits: int = 1, key_value: bool = False, pair_m: Optional[int] = None,
) -> str:
    """Kernel family for one subproblem shape; cached per shape WITH the
    reason it was chosen (:func:`family_decision`), overridable.

    ``digits=2`` keys the decision separately (fused-pair stage solves are
    ``stage_m``-wide; ``m`` here IS the stage width) so autotuning a flat
    shape never re-families a fused-pair plan of ``m == stage_m`` or vice
    versa.  ``key_value``/``pair_m`` are HINTS for the autotune-on-miss
    layer (what to measure), never part of the cache key.

    An explicit ``requested`` family is validated against the backend's
    ``families`` capability and returned verbatim — and, like an explicit
    tile, deliberately NEVER cached: a one-off override must not change
    what later same-shape plans resolve to."""
    from repro.core.pipeline.registry import get_backend

    be = get_backend(backend)
    if requested is not None:
        if requested not in FAMILIES:
            raise ValueError(
                f"unknown kernel family {requested!r}; expected one of {FAMILIES}"
            )
        if be.tiled and requested not in be.families:
            raise ValueError(
                f"backend {backend!r} supports kernel families {be.families}, "
                f"not {requested!r}"
            )
        return requested
    key = _family_key(n, m, method, backend, digits)
    hit = _FAMILY_CACHE.get(key)
    if hit is None:
        from repro.core.pipeline import autotune as _at

        _at.maybe_tune_family(
            n, m, method, backend, digits=digits, key_value=key_value,
            pair_m=pair_m,
        )
        hit = _FAMILY_CACHE.get(key)          # the search pins on success
    if hit is None:
        hit = _heuristic_family(n, m, method, backend)
        _FAMILY_CACHE[key] = hit
    return hit[0]


def family_decision(
    n: int, m: int, method: str, backend: str, digits: int = 1
) -> Tuple[str, str]:
    """(family, reason) for one shape — resolving (and memoizing) it first
    if needed. The reason says whether the heuristic or the autotuner chose,
    and why."""
    resolve_kernel_family(n, m, method, backend, digits=digits)
    return _FAMILY_CACHE[_family_key(n, m, method, backend, digits)]


def family_decisions() -> Dict[Tuple[int, int, str, str], Tuple[str, str]]:
    """Snapshot of every (shape -> (family, reason)) decision so far."""
    return dict(_FAMILY_CACHE)


def resolve_tile(
    n: int,
    m: int,
    method: str,
    key_value: bool,
    backend: str,
    requested: Optional[int] = None,
    family: Optional[str] = None,
    digits: int = 1,
    stage_m: Optional[int] = None,
) -> int:
    """Tile height for one subproblem; cached per shape, overridable.

    ``digits=2`` selects the fused two-digit footprint (DESIGN.md §13): the
    cache gains a digits slot (the single-digit key shape is unchanged) and
    the heuristic charges the DOUBLE-resident tile — two ``stage_m``-wide
    stage solves plus the m-wide pair rows — instead of one m-wide solve.

    The cache key is purely the spec VALUE shape — ``(n, m_eff, method,
    key_value, backend)``, with ``m_eff`` derived from the (hashable)
    bucket spec — never a spec/identifier object id, so equal spec
    instances share one entry and the cache cannot grow per instance
    (regression-tested).  The kernel family the shape auto-resolves to is a
    deterministic function of the same key, so it needs no extra key slot;
    a plan resolved with an EXPLICIT off-heuristic family computes its tile
    under that family's cost model without touching the cache.

    An explicit ``requested`` tile is returned verbatim and deliberately
    NEVER written into the cache: a one-off override must not change what
    later same-shape calls resolve to (regression-tested)."""
    if requested is not None:
        return requested
    kw = dict(digits=digits, stage_m=stage_m, key_value=key_value)
    fam_m = m if digits == 1 else (stage_m or max(1, int(m ** 0.5)))
    auto_family = resolve_kernel_family(
        n, fam_m, method, backend, digits=digits, key_value=key_value,
        pair_m=None if digits == 1 else m,
    )
    fam = auto_family if family is None else family
    if fam != auto_family:
        return _heuristic_tile(n, m, method, backend, family=fam, **kw)
    key = _tile_key(n, m, method, key_value, backend, digits, stage_m)
    tile = _TILE_CACHE.get(key)
    if tile is None:
        from repro.core.pipeline import autotune as _at

        _at.maybe_tune_tile(
            n, m, method, key_value, backend, digits=digits, stage_m=stage_m,
            family=fam,
        )
        tile = _TILE_CACHE.get(key)           # the search pins on success
    if tile is None:
        tile = _heuristic_tile(n, m, method, backend, family=fam, **kw)
        _TILE_CACHE[key] = tile
    return tile


def resolve_sub_bits(
    n: int,
    m: int,
    method: str,
    key_value: bool,
    backend: str,
    stage_m: int,
    requested: Optional[int] = None,
) -> Optional[int]:
    """In-tile sub-digit stage width for a fused-pair plan (DESIGN.md §13):
    the autotuned per-shape width if one was measured (or persisted on
    disk), else ``None`` — the kernels then fall back to the measured
    global default ``_FUSED2_SUB_BITS``. ``m`` is the pair's combined scan
    width (``m_eff``); ``stage_m`` the stage-solve width."""
    if requested is not None:
        return requested
    key = (n, m, method, key_value, backend, stage_m)
    hit = _SUB_BITS_CACHE.get(key)
    if hit is None:
        from repro.core.pipeline import autotune as _at

        _at.maybe_tune_sub_bits(n, m, method, key_value, backend, stage_m)
        hit = _SUB_BITS_CACHE.get(key)
    return hit


def pin_tile(n: int, m: int, method: str, key_value: bool, backend: str,
             tile: int, *, digits: int = 1,
             stage_m: Optional[int] = None) -> None:
    """Pin one tile in the per-shape cache — the degradation ladder's door
    (DESIGN.md §17): when halve-and-retry survives a
    :class:`~repro.runtime.resilience.KernelResourceError`, the survivor is
    pinned here so the shape class never re-learns the OOM the hard way.
    (An EXPLICIT user tile stays uncached — :func:`resolve_tile`'s rule is
    about one-off overrides; a measured resource limit is a shape fact.)"""
    _TILE_CACHE[_tile_key(n, m, method, key_value, backend, digits, stage_m)] \
        = int(tile)


def clear_tile_cache(disk: bool = False) -> None:
    """Drop every memoized tile, family, sub-bits AND label-fusion decision.

    Also drops the lazily-loaded snapshots of the persistent autotune cache
    and the resilience quarantine sidecar, so the next miss re-reads the
    files — i.e. a plain ``clear_tile_cache()`` simulates a fresh process
    against warm cache files (quarantined plan classes SURVIVE the reload,
    DESIGN.md §17).  ``disk=True`` additionally deletes both on-disk
    layers."""
    from repro.core.pipeline import autotune as _at
    from repro.core.pipeline import spec as _spec
    from repro.runtime import resilience as _rz

    _TILE_CACHE.clear()
    _FAMILY_CACHE.clear()
    _SUB_BITS_CACHE.clear()
    _spec._FUSION_CACHE.clear()
    if disk:
        _at.clear_disk()
        _rz.clear_quarantine(disk=True)
    else:
        _at.drop_loaded()
        _rz.drop_loaded()


def autotune_tile(
    n: int,
    bucket_fn: BucketSpec,
    *,
    method: str = "bms",
    key_value: bool = False,
    backend: str = "vmap",
    candidates: Tuple[int, ...] = (256, 512, 1024, 2048, 4096),
    families: Optional[Tuple[str, ...]] = None,
    trials: int = 3,
    seed: int = 0,
    segments: Optional[int] = None,
    batch: Optional[int] = None,
) -> int:
    """Time the candidate (tile, family) grid on synthetic uniform keys and
    pin BOTH winners in the per-shape caches (the family with an
    ``autotuned`` reason naming the measured best), persisting them through
    the autotune disk layer when it is active (DESIGN.md §14). Returns the
    chosen tile; read the family via :func:`family_decision`.

    ``segments=s`` / ``batch=b`` (mutually exclusive) measure the segmented
    or batched layout instead of the flat one — the segmented search pins
    the ``m_eff = s·m`` shape class its plans actually resolve through; the
    batched search times ``b`` rows over the same per-row shape class."""
    import numpy as np

    from repro.core.pipeline import autotune as _at
    from repro.core.pipeline.registry import get_backend
    from repro.core.pipeline.spec import make_plan

    be = get_backend(backend)
    if families is None:
        families = be.families if be.tiled else ("onehot",)
    m_eff = bucket_fn.num_buckets * (segments or 1)
    for fam in families:
        resolve_kernel_family(n, m_eff, method, backend, fam)

    rng = np.random.RandomState(seed)
    shape = (n,) if batch is None else (batch, n)
    keys = jnp.asarray(rng.randint(0, 2**30, shape, dtype=np.uint32))
    values = (jnp.arange(keys.size, dtype=jnp.int32).reshape(shape)
              if key_value else None)
    seg_starts = None
    if segments is not None:
        seg_starts = (jnp.arange(segments, dtype=jnp.int32) * n) // segments
    best, best_t, best_f = None, None, None
    for tile in candidates:
        if tile > max(n, _MIN_TILE):
            continue
        for fam in families:
            plan = make_plan(
                n, bucket_fn.num_buckets, method=method, key_value=key_value,
                backend=backend, tile=tile, bucket_fn=bucket_fn, family=fam,
                segments=segments, batch=batch,
            )
            if segments is not None:
                run = (jax.jit(lambda k, v, p=plan: p(k, v, segment_starts=seg_starts).keys)
                       if key_value else
                       jax.jit(lambda k, p=plan: p(k, segment_starts=seg_starts).keys))
            else:
                run = (jax.jit(lambda k, v, p=plan: p(k, v).keys) if key_value
                       else jax.jit(lambda k, p=plan: p(k).keys))
            args = (keys, values) if key_value else (keys,)
            jax.block_until_ready(run(*args))                # compile
            ts = []
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.block_until_ready(run(*args))
                ts.append(time.perf_counter() - t0)
            t = min(ts)
            if best is None or t < best:
                best, best_t, best_f = t, tile, fam
    if best_t is not None:
        tkey = (n, m_eff, method, key_value, backend)
        _TILE_CACHE[tkey] = best_t
        # The family decision is shared by both key-value variants of the
        # shape, but only THIS variant's tile was measured under the new
        # family — drop the other variant's entry so it re-resolves under
        # the pinned family's cost model instead of keeping a tile sized
        # for the old one (regression-tested).
        _TILE_CACHE.pop((n, m_eff, method, not key_value, backend), None)
        fkey = (n, m_eff, method, backend)
        _FAMILY_CACHE[fkey] = (best_f, (
            f"autotuned over tiles={candidates} x families={tuple(families)}: "
            f"({best_t}, {best_f!r}) won at {best:.3e}s"
        ))
        _at.record("tile", tkey, best_t)
        _at.record("family", fkey, best_f)
    return best_t if best_t is not None else resolve_tile(
        n, bucket_fn.num_buckets * (segments or 1), method, key_value, backend
    )
