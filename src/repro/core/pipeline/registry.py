"""Declarative backend registry for the multisplit pipeline.

PR-1/PR-2 dispatched over {reference, vmap, pallas-interpret, pallas} with
``if backend.startswith("pallas") ... else ...`` chains inlined into every
stage method of the plan. This module replaces those chains with data: a
:class:`Backend` descriptor per execution target, registered once, looked up
by name. A backend bundles

* capability flags (``tiled``, ``fuses_radix``, ``key_itemsize``) that the
  stage graph consults instead of string-matching the backend name, and
* a :class:`StageImpl` — the backend's implementations of the three local
  pipeline stages (prescan / postscan-positions / postscan-reorder) over
  pre-tiled buffers.

Adding an execution target (e.g. a Triton port, or a compiled-CPU pallas
variant) is one ``register_backend`` call; nothing in the stage graph, the
consumers, or the chained radix pipeline changes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.pipeline import stages as _st

Array = jnp.ndarray


class StageImpl:
    """Backend implementations of the local pipeline stages.

    All methods operate on PRE-TILED ``(L, tile)`` buffers. ``spec`` is the
    resolved :class:`~repro.core.pipeline.spec.PipelineSpec`; the segmented
    layout is selected by ``seg_tiled is not None`` and the fused radix
    identifier by ``spec.radix`` (digits never exist host-side on kernel
    backends).
    """

    def map_batch(self, spec, n_tiles: int, tile: int) -> int:
        """The ``lax.map`` batch size of the tile stages over ``n_tiles``
        tiles of ``tile`` keys; 0 where they are not run in chunks."""
        return 0

    def prescan(self, spec, keys_tiled, ids_tiled, seg_tiled) -> Array:
        raise NotImplementedError

    def positions(self, spec, g, keys_tiled, ids_tiled, seg_tiled) -> Array:
        raise NotImplementedError

    def reorder(self, spec, g, keys_tiled, ids_tiled, vals_tiled, seg_tiled):
        raise NotImplementedError


class KernelStages(StageImpl):
    """Pallas kernel stages (interpreted on CPU or compiled for TPU).

    One fused VMEM pass per tile; segment ids ride inside the kernels
    (DESIGN.md §4, §5, §9). ``ids_tiled is None`` selects the fused-label
    path (DESIGN.md §11): bucket ids are computed IN-KERNEL from the plan's
    hashable :class:`~repro.core.identifiers.BucketSpec` (the radix digit is
    just ``BitfieldSpec``), so no label strip exists outside the kernel.
    Only :class:`~repro.core.identifiers.CallableSpec` plans feed the
    kernels precomputed ``ids_tiled``.

    ``compiled=True`` marks the Mosaic-lowering target: its ``interpret``
    flag is RESOLVED per call (DESIGN.md §15) — compiled when a TPU is
    attached, interpreted otherwise, ``REPRO_INTERPRET`` overriding both —
    so ``backend="pallas"`` means compiled-when-available while
    ``backend="pallas-interpret"`` stays the pinned debug target.
    """

    def __init__(self, compiled: bool = False):
        self.compiled = compiled

    @property
    def interpret(self) -> bool:
        from repro.kernels import ops as kops

        return kops.resolve_interpret(self.compiled)

    def prescan(self, spec, keys_tiled, ids_tiled, seg_tiled):
        from repro.kernels import ops as kops

        m, s = spec.num_buckets, spec.segments
        if spec.digit_split is not None:         # fused two-digit pair (§13)
            return kops.fused2_tile_histograms(
                keys_tiled, seg_tiled, spec=spec.bucket_fn,
                num_segments=s or 1, interpret=self.interpret,
            )
        if spec.family == "packed":              # packed-counter family (§12)
            return kops.packed_tile_histograms(
                keys_tiled if ids_tiled is None else ids_tiled, seg_tiled,
                num_buckets=m,
                spec=spec.bucket_fn if ids_tiled is None else None,
                num_segments=s or 1, interpret=self.interpret,
            )
        if ids_tiled is None:                    # fused labels in-kernel
            if seg_tiled is not None:
                return kops.seg_spec_tile_histograms(
                    keys_tiled, seg_tiled, spec.bucket_fn, s, interpret=self.interpret
                )
            return kops.spec_tile_histograms(
                keys_tiled, spec.bucket_fn, interpret=self.interpret
            )
        if seg_tiled is not None:
            return kops.seg_tile_histograms(
                ids_tiled, seg_tiled, m, s, interpret=self.interpret
            )
        return kops.tile_histograms(ids_tiled, m, interpret=self.interpret)

    def positions(self, spec, g, keys_tiled, ids_tiled, seg_tiled):
        from repro.kernels import ops as kops

        m, s = spec.num_buckets, spec.segments
        if spec.digit_split is not None:         # fused two-digit pair (§13)
            return kops.fused2_tile_positions(
                keys_tiled, g, seg_tiled, spec=spec.bucket_fn,
                split=spec.digit_split, num_segments=s or 1,
                family=spec.family, sub_bits=spec.sub_bits,
                interpret=self.interpret,
            )
        if spec.family == "packed":              # packed-counter family (§12)
            return kops.packed_tile_positions(
                keys_tiled if ids_tiled is None else ids_tiled, g, seg_tiled,
                num_buckets=m,
                spec=spec.bucket_fn if ids_tiled is None else None,
                num_segments=s or 1, interpret=self.interpret,
            )
        if ids_tiled is None:                    # fused labels in-kernel
            if seg_tiled is not None:
                return kops.seg_spec_tile_positions(
                    keys_tiled, seg_tiled, g, spec.bucket_fn, s,
                    interpret=self.interpret,
                )
            return kops.spec_tile_positions(
                keys_tiled, g, spec.bucket_fn, interpret=self.interpret
            )
        if seg_tiled is not None:
            return kops.seg_tile_positions(
                ids_tiled, seg_tiled, g, m, s, interpret=self.interpret
            )
        return kops.tile_positions(ids_tiled, g, m, interpret=self.interpret)

    def reorder(self, spec, g, keys_tiled, ids_tiled, vals_tiled, seg_tiled):
        from repro.kernels import ops as kops

        m, s = spec.num_buckets, spec.segments
        if spec.digit_split is not None:         # fused two-digit pair (§13)
            return kops.fused2_fused_postscan_reorder(
                keys_tiled, g, vals_tiled, seg_tiled, spec=spec.bucket_fn,
                split=spec.digit_split, num_segments=s or 1,
                family=spec.family, sub_bits=spec.sub_bits,
                interpret=self.interpret,
            )
        if spec.family == "packed":              # packed-counter family (§12)
            fused = ids_tiled is None
            return kops.packed_fused_postscan_reorder(
                keys_tiled if fused else ids_tiled, g,
                keys_tiled=None if fused else keys_tiled,
                values_tiled=vals_tiled, seg_tiled=seg_tiled,
                num_buckets=m, spec=spec.bucket_fn if fused else None,
                num_segments=s or 1, interpret=self.interpret,
            )
        if ids_tiled is None:                    # fused labels in-kernel
            if seg_tiled is not None:
                return kops.seg_spec_fused_postscan_reorder(
                    keys_tiled, seg_tiled, g, vals_tiled, spec.bucket_fn, s,
                    interpret=self.interpret,
                )
            return kops.spec_fused_postscan_reorder(
                keys_tiled, g, vals_tiled, spec.bucket_fn, interpret=self.interpret
            )
        if seg_tiled is not None:
            return kops.seg_fused_postscan_reorder(
                ids_tiled, seg_tiled, g, keys_tiled, vals_tiled, m, s,
                interpret=self.interpret,
            )
        return kops.fused_postscan_reorder(
            ids_tiled, g, keys_tiled, vals_tiled, m, interpret=self.interpret
        )


# Working-set bound for one XLA program of the vmap backend's tile stages.
# vmapping a per-tile body over ALL tiles materializes its T×m̄ planes for
# the whole input at once (n·m̄ words: 8 GiB of packed words at n = 2^25,
# m = 256, more than a v5e chip's HBM), so past this bound the tiles run in
# chunks through ``lax.map`` — same bodies, same results.
_VMAP_WORKSET_BYTES = 1 << 30


def _map_batch(spec, n_tiles: int, t: int) -> int:
    """Tiles per ``lax.map`` chunk where the vmapped working set of
    ``n_tiles`` tiles of ``t`` keys would pass ``_VMAP_WORKSET_BYTES``, else
    0. The per-tile estimate is three int32 planes of the local solve's
    width: the sub-digit stage planes plus the pair rows for a fused pair
    (DESIGN.md §13), else the m_eff-wide one-hot/packed planes."""
    if spec.digit_split is not None:
        per_tile = 12 * (t * 16 * (spec.segments or 1) + spec.m_eff)
    else:
        per_tile = 12 * t * spec.m_eff
    chunk = _VMAP_WORKSET_BYTES // max(per_tile, 1)
    return 0 if chunk >= n_tiles else max(1, chunk)


def _over_tiles(spec, *tiled):
    """``jax.vmap`` over the tile axis, or ``lax.map`` over chunks of tiles
    (:func:`_map_batch`)."""
    batch = _map_batch(spec, *next(x for x in tiled if x is not None).shape)
    if not batch:
        return jax.vmap

    def chunked(fn):
        return lambda *xs: jax.lax.map(lambda a: fn(*a), xs, batch_size=batch)

    return chunked


class VmapStages(StageImpl):
    """Tiled jnp stages: the SAME fusion as the kernels — local ranks, tile
    starts, tile destination and global destination all from one
    one-hot/cumsum evaluation per tile. Segmented tiles swap the one-hot for
    its segmented-carry form + a scatter-add histogram, keeping the pass
    O(T·m) instead of O(T·s·m) (DESIGN.md §9).

    Fused-label plans (``ids_tiled is None``, DESIGN.md §11) derive the tile
    label strip from ``spec.bucket_fn.emit`` INSIDE the vmapped stage — the
    labels are an XLA-fused intermediate of the per-tile computation, never
    a host/plan-layer array (bitwise identical to the ids path).
    """

    def map_batch(self, spec, n_tiles, tile):
        return _map_batch(spec, n_tiles, tile)

    @staticmethod
    def _tile_ids(spec, keys_tiled, ids_tiled):
        if ids_tiled is not None:
            return ids_tiled
        return jax.vmap(spec.bucket_fn.emit)(keys_tiled)  # n-sized, no planes

    @staticmethod
    def _local_offsets(spec, ids, m):
        """Per-tile local solve of the plan's kernel family: dense one-hot
        cumsum, or the lane-packed two-level rank (bitwise identical)."""
        if spec.family == "packed":
            return _st.packed_tile_local_offsets(ids, m)
        return _st.tile_local_offsets(ids, m)

    @staticmethod
    def _fused2_kw(spec):
        bf = spec.bucket_fn
        return dict(shift=bf.shift, split=spec.digit_split, bits=bf.bits,
                    num_segments=spec.segments or 1, family=spec.family,
                    sub_bits=spec.sub_bits)

    def prescan(self, spec, keys_tiled, ids_tiled, seg_tiled):
        _tiles = _over_tiles(spec, keys_tiled, ids_tiled)
        m = spec.num_buckets
        if spec.digit_split is not None:         # fused two-digit pair (§13)
            bf, s = spec.bucket_fn, spec.segments or 1
            if seg_tiled is not None:
                return _tiles(lambda k, sg: _st.fused2_tile_counts(
                    k, bf.shift, bf.bits, seg=sg, num_segments=s
                ))(keys_tiled, seg_tiled)
            return _tiles(lambda k: _st.fused2_tile_counts(
                k, bf.shift, bf.bits
            ))(keys_tiled)
        ids_tiled = self._tile_ids(spec, keys_tiled, ids_tiled)
        if seg_tiled is not None:
            m_eff = spec.m_eff
            cid = (seg_tiled * m + ids_tiled).astype(jnp.int32)
            if spec.family == "packed" and spec.mode != "counts_only":
                # same expression the packed postscan evaluates, so XLA CSEs
                # the two stages under one jit; for counts_only (no postscan
                # follows) the O(T) scatter-add below stays the cheapest form
                return _tiles(
                    lambda c: _st.packed_tile_local_offsets(c, m_eff)[1]
                )(cid)
            return _tiles(lambda c: _st.direct_counts(c, m_eff))(cid)
        if spec.mode == "counts_only":
            # histogram path: an O(T) scatter-add per tile — the O(T·m)
            # one-hot below buys nothing when no postscan follows
            return _tiles(lambda t: _st.direct_counts(t, m))(ids_tiled)
        return _tiles(lambda t: self._local_offsets(spec, t, m)[1])(ids_tiled)

    def positions(self, spec, g, keys_tiled, ids_tiled, seg_tiled):
        _tiles = _over_tiles(spec, keys_tiled, ids_tiled)
        m = spec.num_buckets
        if spec.digit_split is not None:         # fused two-digit pair (§13)
            kw = self._fused2_kw(spec)
            if seg_tiled is not None:
                return _tiles(lambda k, sg, gt: _st.fused2_tile_postscan(
                    k, gt, None, seg=sg, **kw
                )[3])(keys_tiled, seg_tiled, g)
            return _tiles(lambda k, gt: _st.fused2_tile_postscan(
                k, gt, None, **kw
            )[3])(keys_tiled, g)
        ids_tiled = self._tile_ids(spec, keys_tiled, ids_tiled)
        if seg_tiled is not None:
            m_eff = spec.m_eff

            def one_tile_seg(ids, segs, g_tile):
                cid = (segs * m + ids).astype(jnp.int32)
                if spec.family == "packed":
                    local = _st.packed_tile_local_offsets(cid, m_eff)[0]
                else:
                    local = _st.seg_tile_local(ids, segs, m)
                return g_tile[cid] + local

            return _tiles(one_tile_seg)(ids_tiled, seg_tiled, g)

        def one_tile(ids, g_tile):
            local, _ = self._local_offsets(spec, ids, m)
            return g_tile[ids] + local

        return _tiles(one_tile)(ids_tiled, g)

    def reorder(self, spec, g, keys_tiled, ids_tiled, vals_tiled, seg_tiled):
        _tiles = _over_tiles(spec, keys_tiled, ids_tiled)
        m, m_eff = spec.num_buckets, spec.m_eff
        if spec.digit_split is not None:         # fused two-digit pair (§13)
            kw = self._fused2_kw(spec)

            def fused2_tile(k, sg, gt, vt):
                keys_r, vals_r, pos_r, perm = _st.fused2_tile_postscan(
                    k, gt, vt, seg=sg, **kw
                )
                if vt is None:
                    return keys_r, pos_r, perm
                return keys_r, vals_r, pos_r, perm

            if vals_tiled is None:
                keys_r, pos_r, perm = _tiles(
                    lambda k, gt: fused2_tile(k, None, gt, None)
                )(keys_tiled, g) if seg_tiled is None else _tiles(
                    lambda k, sg, gt: fused2_tile(k, sg, gt, None)
                )(keys_tiled, seg_tiled, g)
                return keys_r, None, pos_r, perm
            if seg_tiled is None:
                return _tiles(
                    lambda k, gt, vt: fused2_tile(k, None, gt, vt)
                )(keys_tiled, g, vals_tiled)
            return _tiles(fused2_tile)(keys_tiled, seg_tiled, g, vals_tiled)
        ids_tiled = self._tile_ids(spec, keys_tiled, ids_tiled)

        def fused_tile(ids, segs, g_tile, keys_t, vals_t):
            if segs is None:
                local, hist = self._local_offsets(spec, ids, m)
                cid = ids
            elif spec.family == "packed":
                cid = (segs * m + ids).astype(jnp.int32)
                local, hist = _st.packed_tile_local_offsets(cid, m_eff)
            else:
                local = _st.seg_tile_local(ids, segs, m)
                cid = (segs * m + ids).astype(jnp.int32)
                hist = _st.direct_counts(cid, m_eff)
            starts = (jnp.cumsum(hist) - hist).astype(jnp.int32)
            dest = starts[cid] + local
            pos = (g_tile[cid] + local).astype(jnp.int32)
            keys_r = jnp.zeros_like(keys_t).at[dest].set(keys_t)
            pos_r = jnp.zeros_like(pos).at[dest].set(pos)
            if vals_t is None:
                return keys_r, pos_r, pos
            vals_r = jnp.zeros_like(vals_t).at[dest].set(vals_t)
            return keys_r, vals_r, pos_r, pos

        if seg_tiled is None:
            if vals_tiled is None:
                keys_r, pos_r, perm = _tiles(
                    lambda i, gt, kt: fused_tile(i, None, gt, kt, None)
                )(ids_tiled, g, keys_tiled)
                return keys_r, None, pos_r, perm
            keys_r, vals_r, pos_r, perm = _tiles(
                lambda i, gt, kt, vt: fused_tile(i, None, gt, kt, vt)
            )(ids_tiled, g, keys_tiled, vals_tiled)
            return keys_r, vals_r, pos_r, perm
        if vals_tiled is None:
            keys_r, pos_r, perm = _tiles(
                lambda i, sg, gt, kt: fused_tile(i, sg, gt, kt, None)
            )(ids_tiled, seg_tiled, g, keys_tiled)
            return keys_r, None, pos_r, perm
        keys_r, vals_r, pos_r, perm = _tiles(fused_tile)(
            ids_tiled, seg_tiled, g, keys_tiled, vals_tiled
        )
        return keys_r, vals_r, pos_r, perm


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered execution target for the pipeline stage graph.

    ``tiled=False`` marks a direct-solve backend (no tiling, no scan — the
    O(n·m) oracle); ``stages`` is then unused. ``fuses_labels`` advertises
    fused-label execution (DESIGN.md §11): any fusable
    :class:`~repro.core.identifiers.BucketSpec` is evaluated inside the
    backend's tile stage and never materialized as a plan-layer label array.
    ``fuses_radix`` is the pre-PR-4 kernel-only flag (in-KERNEL digit
    extraction), kept for introspection compat; ``fuses_digits`` advertises
    the fused TWO-digit radix stage (DESIGN.md §13: both digit solves and
    the intermediate reorder happen per tile residency, dispatched when the
    plan carries a ``digit_split``); ``key_itemsize`` restricts
    key width (pallas kernels are 32-bit-lane programs). ``families`` lists
    the kernel families (DESIGN.md §12) the backend's stages implement;
    :func:`~repro.core.pipeline.tiles.resolve_kernel_family` validates
    explicit requests against it and auto-resolves within it.
    ``tunable_axes`` names the knobs the self-tuning layer (DESIGN.md §14)
    may search for this backend: ``"tile"`` / ``"family"`` / ``"sub_bits"``
    (the fused-pair in-tile stage width) / ``"fusion"`` (the vmap
    materialize-vs-fuse label choice — kernel backends always fuse, so it
    is not an axis there). The untiled oracle has none.
    ``compiled`` advertises Mosaic lowering capability (DESIGN.md §15): the
    backend's kernel bodies are gather/scatter-free (jaxpr-linted) and its
    ``interpret`` flag resolves per call — compiled on TPU hardware,
    interpreted on hosts, ``REPRO_INTERPRET`` overriding.
    """

    name: str
    description: str
    stages: Optional[StageImpl] = None
    tiled: bool = True
    uses_kernels: bool = False
    compiled: bool = False
    fuses_radix: bool = False
    fuses_labels: bool = False
    fuses_digits: bool = False
    key_itemsize: Optional[int] = None
    families: Tuple[str, ...] = ("onehot",)
    tunable_axes: Tuple[str, ...] = ()

    def check_keys(self, keys: Array) -> None:
        if self.key_itemsize is not None and keys.dtype.itemsize != self.key_itemsize:
            raise ValueError(
                f"backend {self.name!r} requires {8 * self.key_itemsize}-bit keys "
                f"(got {keys.dtype}); use backend='vmap' for other widths"
            )


_REGISTRY: dict = {}


def register_backend(backend: Backend) -> Backend:
    if backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; expected one of {backend_names()}"
        ) from None


def available_backends() -> Tuple[Backend, ...]:
    return tuple(_REGISTRY.values())


def backend_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


register_backend(Backend(
    name="reference",
    description="O(n·m) direct evaluation of paper eq. (1); the oracle",
    tiled=False,
    families=("onehot", "packed"),   # packed: the lane-packed direct oracle
))
register_backend(Backend(
    name="vmap",
    description="tiled jnp stages, fused per-tile closure",
    stages=VmapStages(),
    fuses_labels=True,
    fuses_digits=True,
    families=("onehot", "packed"),
    tunable_axes=("tile", "family", "fusion", "sub_bits"),
))
register_backend(Backend(
    name="pallas-interpret",
    description="Pallas kernels interpreted on CPU (pinned debug target)",
    stages=KernelStages(compiled=False),
    uses_kernels=True,
    fuses_radix=True,
    fuses_labels=True,
    fuses_digits=True,
    key_itemsize=4,
    families=("onehot", "packed"),
    tunable_axes=("tile", "family", "sub_bits"),
))
register_backend(Backend(
    name="pallas",
    description="Pallas kernels, Mosaic-compiled when a TPU is attached",
    stages=KernelStages(compiled=True),
    uses_kernels=True,
    compiled=True,
    fuses_radix=True,
    fuses_labels=True,
    fuses_digits=True,
    key_itemsize=4,
    families=("onehot", "packed"),
    tunable_axes=("tile", "family", "sub_bits"),
))

# Compatibility tuple: the registered names, reference first (PR-1 order).
BACKENDS = backend_names()


def capability_summary() -> dict:
    """Registry + resilience state in one introspection dict (the CI
    registry step-summary unit, DESIGN.md §17): per-backend capability
    flags plus the runtime-verification level, strict-mode state, demotion
    order, quarantined plan-class count, and the degradation/verification
    counters."""
    from repro.runtime import resilience as _rz

    backends = {}
    for b in available_backends():
        backends[b.name] = {
            "description": b.description,
            "caps": [k for k in ("tiled", "uses_kernels", "fuses_radix",
                                 "fuses_digits", "compiled") if getattr(b, k)],
            "families": list(b.families),
            "digits": [1, 2] if b.fuses_digits else [1],
            "tunable": list(b.tunable_axes),
            "demotes_to": _rz.demote(b.name),
        }
    return {
        "backends": backends,
        "resilience": {
            "verify": _rz.verify_level(),
            "strict": _rz.strict(),
            "demotion_order": list(_rz.DEMOTION_ORDER),
            "breaker_threshold": _rz.BREAKER_THRESHOLD,
            "quarantined": len(_rz.quarantine_snapshot()),
            "counters": _rz.stats(),
        },
    }


# (n, key dtype) -> (backend, reason) of each default_backend call so far.
_BACKEND_DECISIONS: dict = {}


def _auto_partitioned() -> bool:
    """Whether the call is traced under a mesh whose partitioner would have
    to split it: an axis of more than one device that no ``shard_map`` has
    made manual. A Mosaic kernel cannot be partitioned automatically."""
    mesh = jax.sharding.get_abstract_mesh()
    return not mesh.empty and any(
        size > 1 and kind != jax.sharding.AxisType.Manual
        for size, kind in zip(mesh.axis_sizes, mesh.axis_types))


def default_backend(n: int, key_dtype) -> str:
    """The backend of a call that names none: ``pallas`` where the kernels
    lower compiled (a TPU is attached and ``REPRO_INTERPRET`` does not force
    interpret mode), the keys are 32-bit, the only width they take, and no
    automatically partitioned mesh axis would have to split the kernels
    (inside ``shard_map`` they run per device); else ``vmap``. Each decision
    is recorded with its reason (:func:`backend_decisions`)."""
    from repro.kernels import ops as kops

    dtype = jnp.dtype(key_dtype)
    bits = 8 * dtype.itemsize
    if not kops._tpu_available():
        backend, reason = "vmap", "no TPU"
    elif kops.resolve_interpret(True):
        backend, reason = "vmap", "REPRO_INTERPRET"
    elif bits != 32:
        backend, reason = "vmap", f"{bits}-bit keys"
    elif _auto_partitioned():
        backend, reason = "vmap", "auto-partitioned mesh"
    else:
        backend, reason = "pallas", "tpu+32-bit keys"
    _BACKEND_DECISIONS[(n, dtype.name)] = (backend, reason)
    return backend


def backend_decisions() -> dict:
    """Snapshot of every ((n, key dtype) -> (backend, reason)) default so
    far."""
    return dict(_BACKEND_DECISIONS)


def backend_for(backend: Optional[str], n: int, key_dtype) -> str:
    """The backend a call runs on: the one named (validated against the
    registry), else :func:`default_backend` of its ``n`` and key dtype.
    Every public entry point resolves its ``backend`` argument here."""
    if backend is not None:
        return get_backend(backend).name
    return default_backend(n, key_dtype)
