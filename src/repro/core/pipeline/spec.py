"""PipelineSpec + the executable plan: the stage graph of the multisplit
pipeline (paper §4.1), with partial-pipeline modes.

A :class:`PipelineSpec` declares WHAT to run — problem shape, method, layout
(flat / batched / segmented), backend name, and ``mode``:

* ``mode="reorder"`` (default): the full {prescan, scan, postscan+reorder,
  scatter} pipeline — stable bucket-major output.
* ``mode="counts_only"``: {prescan, tree-reduce} — the paper's §7.3
  device-wide histogram. No scan, no scatter, no output permutation.
* ``mode="positions_only"``: {prescan, scan, postscan-positions} — the
  eq. (2) destination map WITHOUT materializing reordered keys (what MoE
  dispatch and length-bucketing consume).

:class:`MultisplitPlan` executes a spec by composing the stage
implementations of the registered backend
(:mod:`repro.core.pipeline.registry`) over the layout primitives of
:mod:`repro.core.pipeline.stages`. Its :meth:`MultisplitPlan.run_tiled` runs
one full sweep over PRE-TILED buffers — the unit the chained radix pipeline
(:mod:`repro.core.pipeline.radix`) iterates without re-padding per pass.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.identifiers import BitfieldSpec, BucketSpec, as_spec
from repro.core.pipeline import stages as _st
from repro.core.pipeline.registry import get_backend
from repro.core.pipeline.stages import MultisplitResult
from repro.core.pipeline.tiles import (
    resolve_kernel_family,
    resolve_sub_bits,
    resolve_tile,
)
from repro.runtime import tracing

Array = jnp.ndarray

MODES = ("reorder", "counts_only", "positions_only")

# Fused-label ceiling for NON-kernel (vmap-emulation) backends. The vmap
# stage implementations re-evaluate the bucket spec in EVERY tile stage
# (prescan and postscan), so wide scans pay the spec twice while the
# materialized path pays it once plus the n-sized label traffic. Measured
# host-bench crossover (bench_multisplit.py fused_labels sweep re-run at
# n ∈ {2^18, 2^20}, key-value flat): fused wins up to m=256 (1.03–1.06×)
# and loses from m=512 (0.95–0.97×). Kernel backends fuse in-register and
# always win; the radix BitfieldSpec is a shift-and-mask and always wins
# (measured 1.10× at m=256) — neither consults this ceiling.
VMAP_FUSION_MAX_BUCKETS = 512

# (backend, spec kind, m_eff) -> (fused?, reason) — recorded so a surprising
# execution path can be interrogated, mirroring tiles.family_decision.
_FUSION_CACHE: dict = {}


def fusion_decision(backend: str, spec_kind: str, m_eff: int):
    """(fused?, reason) recorded for one (backend, spec-kind, m_eff) shape by
    :meth:`PipelineSpec.label_fusion`, or None if that shape never decided."""
    return _FUSION_CACHE.get((backend, spec_kind, m_eff))


def fusion_decisions() -> dict:
    """Snapshot of every recorded label-fusion decision so far."""
    return dict(_FUSION_CACHE)


class Stage(NamedTuple):
    """One node of a spec's stage graph: ``name`` is the pipeline role
    (layout / prescan / scan / postscan / reduce / scatter / direct-solve),
    ``impl`` the resolved implementation tag."""

    name: str
    impl: str


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """A declarative multisplit pipeline for one problem shape.

    Frozen and hashable BY VALUE (since PR-4 ``bucket_fn`` holds a hashable
    :class:`~repro.core.identifiers.BucketSpec`, so two plans resolved from
    equal specs are equal — jit caches keyed on a plan never retrace across
    identifier instances).  Build via :func:`make_plan` /
    :func:`make_radix_plan`; the latter sets ``bucket_fn`` to the
    :class:`~repro.core.identifiers.BitfieldSpec` digit.

    Label fusion (DESIGN.md §11) is decided per call by
    :meth:`label_fusion`: on fusing backends every fusable (non-callable)
    spec is evaluated INSIDE the tile stage — in-register in the pallas
    kernels — and the n-sized label array never exists.  Only
    :class:`~repro.core.identifiers.CallableSpec` plans materialize labels,
    through the single :meth:`_host_labels` door.

    ``batch``/``segments`` (mutually exclusive) select the batched or
    segmented layout (DESIGN.md §9): ``batch=b`` expects ``(b, n)`` inputs;
    ``segments=s`` expects flat ``(n,)`` inputs plus a ``segment_starts``
    call argument of shape ``(s,)``. ``mode`` selects how much of the
    pipeline runs (module docstring / DESIGN.md §10).

    ``family`` (DESIGN.md §12) selects the KERNEL FAMILY of the local
    solve — ``"onehot"`` (dense T×m one-hot/cumsum) or ``"packed"``
    (bit-packed subword counters, two-level rank). Resolved by
    :func:`~repro.core.pipeline.tiles.resolve_kernel_family` at
    :func:`make_plan` time, so it is a concrete hashable plan field: equal
    specs keep hashing equal and jit caches keyed on a plan never retrace
    across family-equal resolutions. The two families are bitwise-identical
    (property-tested); the field changes execution cost only.

    ``digit_split`` (DESIGN.md §13) marks a FUSED TWO-DIGIT radix plan: the
    bucket spec is the combined ``2r``-bit pair
    :class:`~repro.core.identifiers.BitfieldSpec` and ``digit_split`` the
    low-digit width ``r``, so the tile stage runs the digit-``d`` solve, a
    stable in-VMEM reorder, and the digit-``d+1`` solve per residency —
    bitwise identical to the plain ``2r``-bit plan (the LSD identity:
    two chained stable passes == one stable pass by the combined digit),
    but with ``r``-wide local solves instead of an ``m²``-wide one.
    """

    n: int
    num_buckets: int
    method: str                     # dms | wms | bms
    key_value: bool
    backend: str
    tile: int
    bucket_fn: Optional[BucketSpec] = None
    batch: Optional[int] = None                    # leading (b, n) axis
    segments: Optional[int] = None                 # ragged segments over (n,)
    mode: str = "reorder"
    family: str = "onehot"
    digit_split: Optional[int] = None              # fused pair low-digit width
    # In-tile sub-digit stage width of the fused-pair LSD sweep (DESIGN.md
    # §13/§14): None = the measured global default (_FUSED2_SUB_BITS); an
    # autotuned per-shape width otherwise. Always None on digits=1 plans.
    sub_bits: Optional[int] = None

    # -- resolved properties ----------------------------------------------
    @property
    def m_eff(self) -> int:
        """Width of the one-hot/scan: ``s*m`` for segmented plans, else m."""
        return self.num_buckets * (self.segments or 1)

    @property
    def radix(self) -> Optional[Tuple[int, int]]:
        """(shift, bits) when the spec is the radix digit, else None (the
        pre-PR-4 introspection surface; the digit is just a BitfieldSpec)."""
        if isinstance(self.bucket_fn, BitfieldSpec):
            return (self.bucket_fn.shift, self.bucket_fn.bits)
        return None

    def ids_fn(self) -> BucketSpec:
        if self.bucket_fn is None:
            raise ValueError("plan has no bucket spec")
        return self.bucket_fn

    @property
    def layout(self) -> str:
        """flat | batched | segmented — the spec's input layout name."""
        if self.segments is not None:
            return "segmented"
        return "batched" if self.batch is not None else "flat"

    def plan_class(self) -> Tuple:
        """The (spec, shape, layout, mode) identity the resilience layer's
        circuit breaker and quarantine key on (DESIGN.md §17; the backend
        slot is added by the ladder per rung).  Built from the bucket
        spec's stable NAME, never an object id — quarantine entries are
        per-host facts that must mean the same thing across processes."""
        bf = self.bucket_fn
        spec_name = "ids" if bf is None else getattr(
            bf, "name", type(bf).__name__)
        shape = (self.n,) if self.batch is None else (self.batch, self.n)
        return (spec_name, shape, self.num_buckets, self.segments,
                self.method, self.key_value, self.mode)

    def fused_radix(self) -> bool:
        """True when the digit is extracted inside the kernels (no host ids).
        Pre-PR-4 introspection surface; :meth:`label_fusion` is the general
        call-time decision."""
        return self.radix is not None and get_backend(self.backend).fuses_radix

    def label_fusion(self, keys: Array) -> bool:
        """Whether THIS call computes bucket ids inside the tile stage
        (DESIGN.md §11): requires a fusable (non-callable) spec, a
        label-fusing tiled backend, and — on kernel backends — keys of the
        kernel lane width.  When False the plan materializes labels through
        :meth:`_host_labels` (the pre-PR-4 behavior, kept for CallableSpec
        and off-width keys in partial modes).

        Eligible shapes then consult a MEASURED cost decision (recorded with
        its reason — :func:`fusion_decision`): vmap-emulation backends
        re-evaluate the spec per stage, so generic fusable specs materialize
        once the scan width reaches ``VMAP_FUSION_MAX_BUCKETS``; kernel
        backends (in-register labels) and the radix
        :class:`~repro.core.identifiers.BitfieldSpec` (a shift-and-mask,
        and the chained radix pipeline's zero-label-traffic guarantee)
        always fuse."""
        bf = self.bucket_fn
        if bf is None or not bf.fusable:
            return False
        be = get_backend(self.backend)
        if not be.tiled or not be.fuses_labels:
            return False
        if be.key_itemsize is not None and keys.dtype.itemsize != be.key_itemsize:
            return False
        if self.digit_split is not None:
            return True               # fused2 kernels take the KEY strip only
        key = (self.backend, type(bf).__name__, self.m_eff)
        hit = _FUSION_CACHE.get(key)
        if hit is None:
            if isinstance(bf, BitfieldSpec):
                hit = (True, (
                    "radix BitfieldSpec: digit extraction is a shift-and-mask "
                    "(measured 1.10x over materialized at m=256) and chained "
                    "radix guarantees zero label traffic"
                ))
            elif be.uses_kernels:
                hit = (True, "kernel backend: labels are computed in-register")
            else:
                # the only MEASURED branch: when autotuning is armed
                # (DESIGN.md §14), time materialize-vs-fuse for this shape
                # instead of trusting the VMAP_FUSION_MAX_BUCKETS heuristic
                from repro.core.pipeline import autotune as _at

                traced = isinstance(keys, jax.core.Tracer)
                if not traced:
                    hit = _at.maybe_tune_fusion(self)    # pins on success
                if hit is None:
                    fuse = self.m_eff < VMAP_FUSION_MAX_BUCKETS
                    if _at.armed() and (traced or _at._IN_SEARCH):
                        # armed but under a trace (timing impossible here) or
                        # inside another axis's timing search (pinning the
                        # heuristic now would block measuring this shape
                        # later): use it WITHOUT caching — a later eager
                        # call can still measure this shape
                        return fuse
                    hit = (True, (
                        f"m_eff={self.m_eff} < {VMAP_FUSION_MAX_BUCKETS}: "
                        f"in-stage labels beat the n-sized label round trip "
                        f"at this width (measured 1.03-1.06x up to m=256)"
                    )) if fuse else (False, (
                        f"m_eff={self.m_eff} >= {VMAP_FUSION_MAX_BUCKETS}: "
                        f"vmap stages re-evaluate the spec per stage, "
                        f"measured slower than one materialized label pass "
                        f"at this width (0.95-0.97x at m=512)"
                    ))
            _FUSION_CACHE[key] = hit
        return hit[0]

    def _host_labels(self, keys: Array) -> Array:
        """THE single label-materialization door of the tiled layout stage.
        Non-callable specs on fusing backends never pass through here
        (monkeypatch-asserted in tests/test_ops_transforms.py)."""
        return self.ids_fn()(keys)

    def pad_key(self, dtype):
        """Fused-label pad sentinel: a key whose bucket is m-1 (for the
        radix BitfieldSpec: the all-ones key, digit m-1 in EVERY pass, so
        chained passes keep pads at the tail without re-padding)."""
        if self.bucket_fn is not None:
            return self.bucket_fn.pad_key(dtype)
        return (1 << 32) - 1 if dtype == jnp.uint32 else -1

    # -- introspection -----------------------------------------------------
    def stages(self) -> Tuple[str, ...]:
        """Human/test-readable pipeline description (``name:impl`` strings).

        Fused-label stages assume lane-width-compatible keys (the call-time
        fallback for off-width keys in partial modes is not shape-visible
        here); the radix BitfieldSpec keeps its historical ``radix-fused``
        spelling. Packed-family plans (DESIGN.md §12) carry a ``-packed``
        suffix on the local-solve stages."""
        be = get_backend(self.backend)
        kernel = be.uses_kernels
        if self.digit_split is not None and be.tiled:
            # fused two-digit pair plans (§13): one stage tag family, the
            # kernel-ness suffix mirrors the single-digit spellings
            eng = "kernel" if kernel else "vmap"
            fam = f"-{self.family}"
            pre = f"prescan:fused2-pair-{eng}"
            positions = f"postscan:fused2-pair-positions-{eng}{fam}"
            post = (positions if self.method == "dms"
                    else f"postscan:fused2-pair-reorder-{eng}{fam}")
            if self.mode == "counts_only":
                base = (pre, "reduce:counts")
            elif self.mode == "positions_only":
                base = (pre, "scan:global", positions)
            else:
                base = (pre, "scan:global", post, "scatter:bucket-major")
            if self.batch is not None:
                return (f"layout:batched[{self.batch}]",) + base
            if self.segments is not None:
                return (f"layout:segmented[{self.segments}]",) + base
            return base
        fusable = (self.bucket_fn is not None and self.bucket_fn.fusable
                   and be.fuses_labels)
        fused_id = kernel and fusable
        radix_id = fused_id and self.radix is not None
        fam = "-packed" if (be.tiled and self.family == "packed") else ""
        # the vmap counts_only prescan is a plain scatter-add histogram on
        # EITHER family (no local rank is ever computed), so it carries no
        # family tag; the kernel backends do run the packed hist kernel
        pre_fam = fam if (kernel or self.mode != "counts_only") else ""
        pre = ("prescan:radix-fused-kernel" if radix_id
               else "prescan:fused-label-kernel" if fused_id
               else "prescan:kernel" if kernel else "prescan:vmap") + pre_fam
        positions = ("postscan:radix-positions-kernel" if radix_id
                     else "postscan:fused-label-positions-kernel" if fused_id
                     else "postscan:positions-kernel" if kernel
                     else "postscan:positions-vmap") + fam
        if self.method == "dms":
            post = positions
        else:
            post = ("postscan:radix-fused-reorder-kernel" if radix_id
                    else "postscan:fused-label-reorder-kernel" if fused_id
                    else "postscan:fused-reorder-kernel" if kernel
                    else "postscan:fused-reorder-vmap") + fam
        if not be.tiled:
            base = ("direct-solve:reference",)
        elif self.mode == "counts_only":
            base = (pre, "reduce:counts")
        elif self.mode == "positions_only":
            base = (pre, "scan:global", positions)
        else:
            base = (pre, "scan:global", post, "scatter:bucket-major")
        if self.batch is not None:
            return (f"layout:batched[{self.batch}]",) + base
        if self.segments is not None:
            return (f"layout:segmented[{self.segments}]",) + base
        return base

    def stage_graph(self) -> Tuple[Stage, ...]:
        """The stage descriptions as structured nodes."""
        out = []
        for s in self.stages():
            name, _, impl = s.partition(":")
            out.append(Stage(name, impl))
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class MultisplitPlan(PipelineSpec):
    """An executable :class:`PipelineSpec`: call with concrete arrays.

    Each stage runs inside a ``repro.stage.<name>`` span (layout, prescan,
    scan, postscan, scatter; :mod:`repro.runtime.tracing`), so that a
    profiler trace of an eager call puts every device program down to the
    stage that launched it."""

    def _stage(self, name: str, tiles: int):
        """The span of stage ``name`` over ``tiles`` tiles."""
        if not tracing.enabled():
            return tracing.OFF
        impl = get_backend(self.backend).stages
        return tracing.span(
            f"repro.stage.{name}", backend=self.backend, family=self.family,
            tile=self.tile, tiles=tiles,
            map_batch=impl.map_batch(self, tiles, self.tile) if impl else 0,
        )

    # -- stage entry points (delegating to the registered backend) ---------
    def prescan(
        self, keys_tiled: Optional[Array], ids_tiled: Optional[Array],
        seg_tiled: Optional[Array] = None,
    ) -> Array:
        """Stage 1: per-tile (combined) bucket histograms -> H (L, m_eff)."""
        return get_backend(self.backend).stages.prescan(
            self, keys_tiled, ids_tiled, seg_tiled
        )

    def postscan(
        self,
        g: Array,
        keys_tiled: Array,
        ids_tiled: Optional[Array],
        vals_tiled: Optional[Array],
        seg_tiled: Optional[Array] = None,
    ) -> Tuple[Array, Optional[Array], Array, Array]:
        """Stage 3: returns (scatter_src_keys, scatter_src_vals, scatter_pos,
        perm).

        For wms/bms the sources are bucket-major within each tile and the
        positions permuted to match — ONE one-hot/cumsum evaluation per tile
        (the fused kernel / fused closure is the only postscan entry point).
        ``perm`` is the element-ordered destination map (paper eq. (2)), a
        free byproduct of the same evaluation. With ``seg_tiled`` the segment
        id rides through the evaluation as the high part of the combined
        bucket id (in-kernel on kernel backends).
        """
        impl = get_backend(self.backend).stages
        if self.method == "dms":
            pos = impl.positions(self, g, keys_tiled, ids_tiled, seg_tiled)
            return keys_tiled, vals_tiled, pos, pos
        return impl.reorder(self, g, keys_tiled, ids_tiled, vals_tiled, seg_tiled)

    # -- the resident-buffer sweep (the chained-radix building block) ------
    def run_tiled(
        self,
        keys_tiled: Array,
        ids_tiled: Optional[Array] = None,
        vals_tiled: Optional[Array] = None,
        seg_tiled: Optional[Array] = None,
        rows: Optional[int] = None,
    ) -> Tuple[Array, Optional[Array], Array, Array]:
        """One full {prescan, scan, postscan, scatter} sweep over PRE-TILED
        buffers. No padding is performed and no tail is sliced off: returns
        ``(keys_pad, vals_pad, hist, perm_tiled)`` at the full padded length
        (``(b, n_row)`` rows when ``rows=b`` — batched layout with a per-row
        scan/scatter). :class:`~repro.core.pipeline.radix.RadixPipeline`
        iterates this on resident ping-pong buffers, one call per digit
        pass."""
        tiles = keys_tiled.shape[0]
        with self._stage("prescan", tiles):
            hist = self.prescan(keys_tiled, ids_tiled, seg_tiled)
        with self._stage("scan", tiles):
            if rows is None:
                g = _st.global_scan(hist)
            else:
                l_b = hist.shape[0] // rows
                g = jax.vmap(_st.global_scan)(
                    hist.reshape(rows, l_b, hist.shape[-1])
                ).reshape(hist.shape)
        with self._stage("postscan", tiles):
            src_keys, src_vals, pos, perm_tiled = self.postscan(
                g, keys_tiled, ids_tiled, vals_tiled, seg_tiled
            )
        with self._stage("scatter", tiles):
            if rows is None:
                n_total = keys_tiled.size
                scatter_pos = pos.reshape(-1)
                keys_pad = (
                    jnp.zeros((n_total,), keys_tiled.dtype)
                    .at[scatter_pos].set(src_keys.reshape(-1))
                )
                vals_pad = None
                if vals_tiled is not None:
                    vals_pad = (
                        jnp.zeros((n_total,), vals_tiled.dtype)
                        .at[scatter_pos].set(src_vals.reshape(-1))
                    )
                return keys_pad, vals_pad, hist, perm_tiled
            n_row = keys_tiled.size // rows
            pos_rows = pos.reshape(rows, n_row)
            scat = lambda p, src: jnp.zeros((n_row,), src.dtype).at[p].set(src)
            keys_pad = jax.vmap(scat)(pos_rows, src_keys.reshape(rows, n_row))
            vals_pad = None
            if vals_tiled is not None:
                vals_pad = jax.vmap(scat)(pos_rows, src_vals.reshape(rows, n_row))
            return keys_pad, vals_pad, hist, perm_tiled

    # -- layout helpers ----------------------------------------------------
    def _empty_result(self, keys: Array, values: Optional[Array]) -> MultisplitResult:
        """n == 0: every output is empty/zero in the layout's shapes."""
        m = self.num_buckets
        if self.batch is not None:
            shape_cm = (self.batch, m)
            perm = jnp.zeros((self.batch, 0), jnp.int32)
        elif self.segments is not None:
            shape_cm = (self.segments, m)
            perm = jnp.zeros((0,), jnp.int32)
        else:
            shape_cm = (m,)
            perm = jnp.zeros((0,), jnp.int32)
        zeros = jnp.zeros(shape_cm, jnp.int32)
        if self.mode == "counts_only":
            return MultisplitResult(None, None, zeros, zeros, None)
        if self.mode == "positions_only":
            return MultisplitResult(None, None, zeros, zeros, perm)
        return MultisplitResult(keys, values, zeros, zeros, perm)

    def _check_key_width(self, keys: Array) -> None:
        """Kernel backends are 32-bit-lane programs; keys unconditionally
        enter kernels only when the pipeline reorders them. In the partial
        modes, off-width keys simply disable label fusion (labels
        materialize host-side and kernels see nothing but int32 ids)."""
        if self.mode == "reorder":
            get_backend(self.backend).check_keys(keys)

    # -- batched driver ----------------------------------------------------
    def _call_batched(self, keys: Array, values: Optional[Array]) -> MultisplitResult:
        b, n, m = self.batch, self.n, self.num_buckets
        if keys.shape != (b, n):
            raise ValueError(f"batched plan resolved for shape {(b, n)}, got {keys.shape}")
        if values is not None and values.shape != (b, n):
            raise ValueError(
                f"batched plans require values of shape {(b, n)}, got {values.shape}"
            )
        if n == 0:
            return self._empty_result(keys, values)

        be = get_backend(self.backend)
        if not be.tiled:
            ids_fn = self.ids_fn()
            if self.mode == "counts_only":
                counts = jax.vmap(lambda k: _st.direct_counts(ids_fn(k), m))(keys)
                return MultisplitResult(
                    None, None, _st.exclusive_rows(counts), counts, None
                )
            direct = self._direct_solve_ids
            solve = lambda k, v: direct(k, ids_fn(k), m, v)
            if values is None:
                res = jax.vmap(lambda k: solve(k, None))(keys)
            else:
                res = jax.vmap(solve)(keys, values)
            if self.mode == "positions_only":
                return MultisplitResult(
                    None, None, res.bucket_starts, res.bucket_counts, res.permutation
                )
            return res

        self._check_key_width(keys)
        tile = self.tile
        l_b = -(-n // tile)                       # tiles per batch row
        n_row = l_b * tile
        tiles = b * l_b

        # Per-row tiling: each tile belongs to exactly ONE batch row, so a
        # single kernel grid of b*l_b programs covers the whole batch.
        with self._stage("layout", tiles):
            if self.label_fusion(keys):
                keys_tiled = _st.pad_rows(
                    keys, n_row, self.pad_key(keys.dtype)
                ).reshape(tiles, tile)
                ids_tiled = None
            else:
                ids = self._host_labels(keys)
                ids_tiled = _st.pad_rows(ids, n_row, m - 1).reshape(tiles, tile)
                if self.mode != "reorder":
                    keys_tiled = None        # partial modes consume only ids
                else:
                    keys_tiled = _st.pad_rows(keys, n_row, 0).reshape(tiles, tile)
            vals_tiled = None
            if values is not None:
                vals_tiled = _st.pad_rows(values, n_row, 0).reshape(tiles, tile)

        if self.mode == "counts_only":
            with self._stage("prescan", tiles):
                hist = self.prescan(keys_tiled, ids_tiled)
            counts = hist.reshape(b, l_b, m).sum(axis=1).astype(jnp.int32)
            counts = counts.at[:, m - 1].add(n - n_row)          # drop pad sentinels
            return MultisplitResult(None, None, _st.exclusive_rows(counts), counts, None)

        if self.mode == "positions_only":
            with self._stage("prescan", tiles):
                hist = self.prescan(keys_tiled, ids_tiled)
            with self._stage("scan", tiles):
                g = jax.vmap(_st.global_scan)(
                    hist.reshape(b, l_b, m)).reshape(tiles, m)
            with self._stage("postscan", tiles):
                pos = get_backend(self.backend).stages.positions(
                    self, g, keys_tiled, ids_tiled, None
                )
            counts = hist.reshape(b, l_b, m).sum(axis=1).astype(jnp.int32)
            counts = counts.at[:, m - 1].add(n - n_row)
            return MultisplitResult(
                None, None, _st.exclusive_rows(counts), counts,
                pos.reshape(b, n_row)[:, :n],
            )

        keys_rows, vals_rows, hist, perm_tiled = self.run_tiled(
            keys_tiled, ids_tiled, vals_tiled, rows=b
        )
        keys_out = keys_rows[:, :n]
        values_out = vals_rows[:, :n] if values is not None else None
        counts = hist.reshape(b, l_b, m).sum(axis=1).astype(jnp.int32)
        counts = counts.at[:, m - 1].add(n - n_row)              # drop pad sentinels
        return MultisplitResult(
            keys_out, values_out, _st.exclusive_rows(counts), counts,
            perm_tiled.reshape(b, n_row)[:, :n],
        )

    # -- full pipeline -----------------------------------------------------
    def __call__(
        self,
        keys: Array,
        values: Optional[Array] = None,
        segment_starts: Optional[Array] = None,
    ) -> MultisplitResult:
        if (values is not None) != self.key_value:
            raise ValueError(
                f"plan resolved for key_value={self.key_value} but called with "
                f"values={'present' if values is not None else 'absent'}"
            )
        if self.segments is None and segment_starts is not None:
            raise ValueError("plan is not segmented; segment_starts not accepted")

        if self.batch is not None:
            return self._call_batched(keys, values)

        if keys.shape[0] != self.n:
            raise ValueError(f"plan resolved for n={self.n}, got n={keys.shape[0]}")
        m, s = self.num_buckets, self.segments
        m_eff = self.m_eff

        seg_ids = None
        if s is not None:
            if segment_starts is None:
                raise ValueError("segmented plan requires segment_starts")
            segment_starts = jnp.asarray(segment_starts, jnp.int32)
            if segment_starts.shape != (s,):
                raise ValueError(
                    f"plan resolved for {s} segments, got segment_starts shape "
                    f"{segment_starts.shape}"
                )
            seg_ids = _st.segment_ids_from_starts(segment_starts, self.n)

        if self.n == 0:
            return self._empty_result(keys, values)

        be = get_backend(self.backend)
        if not be.tiled:
            return self._call_direct(keys, values, seg_ids, segment_starts)

        self._check_key_width(keys)
        n = self.n
        tiles = -(-n // self.tile)

        # ---- layout stage. Pads ride in (segment s-1,) bucket m-1 at the
        # very tail, so they land after every real element and are sliced off
        # below. Fused-label plans pad with the spec's pad key (bucket m-1 by
        # construction; for the radix digit: the all-ones key, digit m-1 in
        # EVERY pass).
        with self._stage("layout", tiles):
            if self.label_fusion(keys):
                keys_p, _ = _st.pad_to_tiles(keys, self.tile, self.pad_key(keys.dtype))
                keys_tiled = keys_p.reshape(-1, self.tile)
                ids_tiled = None
            else:
                ids = self._host_labels(keys)
                ids_p, _ = _st.pad_to_tiles(ids, self.tile, m - 1)
                ids_tiled = ids_p.reshape(-1, self.tile)
                if self.mode != "reorder":
                    keys_tiled = None        # partial modes consume only ids
                else:
                    keys_p, _ = _st.pad_to_tiles(keys, self.tile, 0)
                    keys_tiled = keys_p.reshape(-1, self.tile)
            seg_tiled = None
            if s is not None:
                seg_p, _ = _st.pad_to_tiles(seg_ids, self.tile, s - 1)
                seg_tiled = seg_p.reshape(-1, self.tile)
            vals_tiled = None
            if values is not None:
                vals_p, _ = _st.pad_to_tiles(values, self.tile, 0)
                vals_tiled = vals_p.reshape(-1, self.tile)
        n_total = tiles * self.tile

        def finalize_counts(hist):
            counts = hist.sum(axis=0).astype(jnp.int32)
            return counts.at[m_eff - 1].add(n - n_total)         # drop pad sentinels

        # ---- partial pipelines: counts_only / positions_only
        if self.mode == "counts_only":
            with self._stage("prescan", tiles):
                hist = self.prescan(keys_tiled, ids_tiled, seg_tiled)
            counts = finalize_counts(hist)
            if s is not None:
                counts = counts.reshape(s, m)
            return MultisplitResult(None, None, _st.exclusive_rows(counts), counts, None)

        if self.mode == "positions_only":
            with self._stage("prescan", tiles):
                hist = self.prescan(keys_tiled, ids_tiled, seg_tiled)
            with self._stage("scan", tiles):
                g = _st.global_scan(hist)
            with self._stage("postscan", tiles):
                pos = be.stages.positions(self, g, keys_tiled, ids_tiled, seg_tiled)
            counts = finalize_counts(hist)
            perm = pos.reshape(-1)[:n].astype(jnp.int32)
            if s is not None:
                counts = counts.reshape(s, m)
                perm = perm - segment_starts[seg_ids]            # segment-LOCAL
            return MultisplitResult(None, None, _st.exclusive_rows(counts), counts, perm)

        # ---- full pipeline: the resident-buffer sweep + tail slice.
        # For segmented plans the combined (seg, bucket)-major order IS the
        # segment-concatenated per-segment bucket-major order, so the same
        # flat scatter lands every segment in its input span.
        keys_pad, vals_pad, hist, perm_tiled = self.run_tiled(
            keys_tiled, ids_tiled, vals_tiled, seg_tiled
        )
        keys_out = keys_pad[:n]
        values_out = vals_pad[:n] if values is not None else None
        counts = finalize_counts(hist)
        perm = perm_tiled.reshape(-1)[:n]
        if s is not None:
            counts = counts.reshape(s, m)
            return MultisplitResult(
                keys_out, values_out, _st.exclusive_rows(counts), counts,
                perm - segment_starts[seg_ids],                  # segment-LOCAL
            )
        return MultisplitResult(
            keys_out, values_out, _st.exclusive_rows(counts), counts, perm
        )

    # -- direct-solve driver (the untiled oracle backend) ------------------
    @property
    def _direct_solve_ids(self):
        """The family's direct solve: dense one-hot, or the lane-packed
        oracle (bitwise identical, DESIGN.md §12)."""
        if self.family == "packed":
            return _st.packed_direct_solve_ids
        return _st.direct_solve_ids

    def _call_direct(
        self, keys, values, seg_ids, segment_starts
    ) -> MultisplitResult:
        m, s = self.num_buckets, self.segments
        ids = self.ids_fn()(keys)
        if s is None:
            if self.mode == "counts_only":
                counts = _st.direct_counts(ids, m)
                return MultisplitResult(
                    None, None, _st.exclusive_rows(counts), counts, None
                )
            res = self._direct_solve_ids(keys, ids, m, values)
            if self.mode == "positions_only":
                return MultisplitResult(
                    None, None, res.bucket_starts, res.bucket_counts, res.permutation
                )
            return res
        cid = (seg_ids * m + ids).astype(jnp.int32)
        if self.mode == "counts_only":
            counts = _st.direct_counts(cid, self.m_eff).reshape(s, m)
            return MultisplitResult(None, None, _st.exclusive_rows(counts), counts, None)
        res = self._direct_solve_ids(keys, cid, self.m_eff, values)
        counts = res.bucket_counts.reshape(s, m)
        perm = res.permutation - segment_starts[seg_ids]
        if self.mode == "positions_only":
            return MultisplitResult(None, None, _st.exclusive_rows(counts), counts, perm)
        return MultisplitResult(
            res.keys, res.values, _st.exclusive_rows(counts), counts, perm
        )


def _validate_layout(batch: Optional[int], segments: Optional[int]) -> None:
    if batch is not None and segments is not None:
        raise ValueError("batch and segments are mutually exclusive plan layouts")
    if batch is not None and batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if segments is not None and segments < 1:
        raise ValueError(f"segments must be >= 1, got {segments}")


def _validate_common(method: str, backend: str, mode: str, key_value: bool) -> None:
    if method not in ("dms", "wms", "bms"):
        raise ValueError(f"unknown multisplit method {method!r}")
    get_backend(backend)                  # raises ValueError on unknown names
    if mode not in MODES:
        raise ValueError(f"unknown pipeline mode {mode!r}; expected one of {MODES}")
    if mode != "reorder" and key_value:
        raise ValueError(
            f"mode={mode!r} never touches values; resolve with key_value=False"
        )


def _validate_digit_split(
    digit_split: Optional[int], bucket_fn, backend: str
) -> None:
    if digit_split is None:
        return
    from repro.core.pipeline.registry import get_backend as _gb

    be = _gb(backend)
    if not be.tiled or not be.fuses_digits:
        raise ValueError(
            f"backend {backend!r} does not fuse digit pairs (fuses_digits="
            f"False); run the pair as a plain combined-digit plan instead"
        )
    if not isinstance(bucket_fn, BitfieldSpec):
        raise ValueError(
            "digit_split requires the combined-pair BitfieldSpec bucket_fn "
            f"(got {type(bucket_fn).__name__})"
        )
    if not 0 < digit_split < bucket_fn.bits:
        raise ValueError(
            f"digit_split must split the pair strictly (0 < split < bits); "
            f"got split={digit_split}, bits={bucket_fn.bits}"
        )


def make_plan(
    n: int,
    num_buckets: int,
    *,
    method: str = "bms",
    key_value: bool = False,
    backend: str = "vmap",
    tile: Optional[int] = None,
    bucket_fn: Optional[BucketSpec] = None,
    batch: Optional[int] = None,
    segments: Optional[int] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
    digit_split: Optional[int] = None,
    sub_bits: Optional[int] = None,
) -> MultisplitPlan:
    """Resolve (n, m, method, key-value-ness, backend, mode) into a staged
    plan.

    ``bucket_fn`` is a :class:`~repro.core.identifiers.BucketSpec`; fusable
    specs run label-fused on fusing backends (DESIGN.md §11).  ``batch=b``
    resolves a batched plan over ``(b, n)`` inputs; ``segments=s`` a
    segmented plan over flat ``(n,)`` inputs with an ``(s,)``
    ``segment_starts`` call argument (mutually exclusive). ``mode`` selects a
    partial pipeline (``counts_only`` / ``positions_only``) or the full
    reorder (module docstring). ``family`` pins the kernel family
    (``"onehot"`` / ``"packed"``, DESIGN.md §12); ``None`` auto-resolves it
    per shape through the cached heuristic/autotune decision."""
    _validate_common(method, backend, mode, key_value)
    _validate_layout(batch, segments)
    if bucket_fn is not None:
        bucket_fn = as_spec(bucket_fn)
    _validate_digit_split(digit_split, bucket_fn, backend)
    m_eff = num_buckets * (segments or 1)
    digits = 1 if digit_split is None else 2
    # the fused-pair local solves are digit_split-wide, not m-wide: family
    # (and tile VMEM cost) follow the STAGE width, the scan width stays m_eff
    fam_m = m_eff if digit_split is None else (1 << digit_split) * (segments or 1)
    resolved_family = resolve_kernel_family(
        n, fam_m, method, backend, family, digits=digits, key_value=key_value,
        pair_m=None if digit_split is None else m_eff,
    )
    resolved_tile = resolve_tile(
        n, m_eff, method, key_value, backend, tile, family=resolved_family,
        digits=digits, stage_m=None if digit_split is None else fam_m,
    )
    resolved_sub = None
    if digit_split is not None:
        resolved_sub = resolve_sub_bits(
            n, m_eff, method, key_value, backend, fam_m, requested=sub_bits
        )
    return MultisplitPlan(
        n=n, num_buckets=num_buckets, method=method, key_value=key_value,
        backend=backend, tile=resolved_tile, bucket_fn=bucket_fn,
        batch=batch, segments=segments, mode=mode, family=resolved_family,
        digit_split=digit_split, sub_bits=resolved_sub,
    )


def make_radix_plan(
    n: int,
    shift: int,
    bits: int,
    *,
    method: str = "bms",
    key_value: bool = False,
    backend: str = "vmap",
    tile: Optional[int] = None,
    batch: Optional[int] = None,
    segments: Optional[int] = None,
    mode: str = "reorder",
    family: Optional[str] = None,
    digit_split: Optional[int] = None,
    sub_bits: Optional[int] = None,
) -> MultisplitPlan:
    """A plan whose bucket spec is the radix digit
    :class:`~repro.core.identifiers.BitfieldSpec`(shift, bits) — label-fused
    into the tile stage on fusing backends (in-register in the kernels; no
    label array anywhere).  ``digit_split=r`` marks ``bits`` as a fused
    TWO-digit pair (low digit ``r`` bits wide, DESIGN.md §13); ``sub_bits``
    pins the pair's in-tile sub-digit stage width (None auto-resolves it,
    DESIGN.md §14)."""
    return make_plan(
        n, 1 << bits, method=method, key_value=key_value, backend=backend,
        tile=tile, bucket_fn=BitfieldSpec(shift, bits), batch=batch,
        segments=segments, mode=mode, family=family, digit_split=digit_split,
        sub_bits=sub_bits,
    )


def make_batched_plan(batch: int, n: int, num_buckets: int, **kw) -> MultisplitPlan:
    """Batched plan over ``(batch, n)`` inputs: one launch for all rows."""
    return make_plan(n, num_buckets, batch=batch, **kw)


def make_segmented_plan(n: int, num_segments: int, num_buckets: int, **kw) -> MultisplitPlan:
    """Segmented plan over flat ``(n,)`` inputs with ``num_segments`` ragged
    segments (call with ``segment_starts=``): one launch for all segments."""
    return make_plan(n, num_buckets, segments=num_segments, **kw)


def make_segmented_radix_plan(
    n: int, num_segments: int, shift: int, bits: int, **kw
) -> MultisplitPlan:
    """Segmented radix plan: one fused digit pass over all segments."""
    return make_radix_plan(n, shift, bits, segments=num_segments, **kw)
