"""Pallas TPU kernels for the multisplit direct solve (paper §4.5, §5.5).

One grid program processes one tile (the paper's subproblem): a VMEM-resident
strip of bucket ids. The GPU ballot/popc machinery is replaced by a one-hot
matrix in VMEM reduced/scanned with MXU-friendly dense ops (DESIGN.md §2);
the shared primitives live in :mod:`repro.kernels.common`.

Kernels:

* ``tile_histograms_pallas``       — prescan direct solve (paper Alg. 2).
* ``tile_positions_pallas``        — postscan for DMS (no reorder): final
                                     destinations only (paper eq. (2)).
* ``fused_postscan_reorder_pallas``— THE WMS/BMS postscan (DESIGN.md §4):
                                     local ranks, global destinations AND the
                                     within-tile bucket-major reorder of keys,
                                     values and destinations from a single
                                     one-hot/cumsum evaluation. This is the
                                     only postscan/reorder entry point of the
                                     fused pipeline — it replaces the three
                                     separate postscan/reorder-keys/
                                     reorder-values passes of the legacy host
                                     orchestration.

Segmented variants (``seg_*``, DESIGN.md §9): identical math, but each tile
additionally carries a per-element SEGMENT id strip. The kernel combines
``cid = seg * m + bucket`` in-register, so the one-hot/cumsum pass ranks
every element within its own (segment, bucket) cell — many independent
ragged multisplits per grid launch, no host-side combined-id array and no
per-segment relaunch.

Fused-label variants (``spec_*``, DESIGN.md §11): the kernels take the KEY
strip plus a hashable :class:`~repro.core.identifiers.BucketSpec` (a static
kernel parameter) and evaluate ``spec.emit_in_kernel(keys)`` *inside* the
kernel —
bucket ids live only in registers/VMEM, exactly the paper's warp-private
bucket computation, for EVERY declarative spec (delta, range/splitter,
even, identity, radix bitfield), not just the radix digit. The n-sized
label array of the pre-PR-4 pipeline never exists for these specs; the
radix kernels in :mod:`repro.kernels.radix_pass` are now thin
``BitfieldSpec`` instantiations of this machinery.

Packed-counter variants (``packed_*``, DESIGN.md §12): the second KERNEL
FAMILY. Same stage contracts as the dense kernels above, but the local
solve uses bit-packed subword counters + two-level (subtile -> tile)
ranking (paper §4.3) instead of the T×m one-hot/cumsum, so per-key work
and VMEM stay ~flat in the bucket count. One generic kernel per stage
covers all four dense shapes ({ids | fused-spec labels} × {flat |
segmented}); family selection is a plan axis resolved by
:func:`repro.core.pipeline.tiles.resolve_kernel_family`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    dense_positions_body,
    _pair_hist2d_shape,
    fused2_counts_2d,
    fused2_counts_body,
    fused2_positions_body,
    fused2_postscan_body,
    fused_postscan_body,
    one_hot_f32 as _one_hot,
    packed_counts,
    packed_layout,
    packed_positions_body,
    packed_postscan_body,
    pad_lanes as _pad_lanes,
)

Array = jnp.ndarray

# Scoped-VMEM limit handed to Mosaic for every tile kernel: twice the tile
# heuristic's budget (core/pipeline/tiles.py ``_VMEM_BUDGET_BYTES``, whose
# cost models bound what the compiler allocates), so every heuristic tile
# compiles with headroom; a v5e TensorCore has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 32 << 20


def _blocked(shape):
    """``(L, w)`` rows travel as ``(L, 1, w)``; ``(L, a, b)`` tables as is."""
    return tuple(shape) if len(shape) == 3 else (shape[0], 1, shape[1])


def _tiled_call(kernel, args, out_shape, *, interpret: bool):
    """ONE grid step per tile over ``(L, w)`` row arrays (or ``(L, a, b)``
    per-tile tables).

    Each row array reaches the kernel as an ``(L, 1, w)`` array in
    ``(None, 1, w)`` blocks: the block's last two dims then equal the
    array's, which is the layout Mosaic accepts for any ``L`` (a ``(1, w)``
    block of an ``(L, w)`` array is refused for ``L > 1``), and the reshape
    is a free bitcast of the row-major data. Kernels index ``ref[0, :]``
    for rows and ``ref[...]`` for tables. ``out_shape`` lists structs of
    either rank; the outputs come back as a list in those shapes."""
    n_tiles = args[0].shape[0]

    def spec(shape):
        return pl.BlockSpec((None,) + shape[1:], lambda i: (i, 0, 0))

    outs = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[spec(_blocked(a.shape)) for a in args],
        out_specs=[spec(_blocked(o.shape)) for o in out_shape],
        out_shape=[jax.ShapeDtypeStruct(_blocked(o.shape), o.dtype)
                   for o in out_shape],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*[a.reshape(_blocked(a.shape)) for a in args])
    return [o.reshape(s.shape) for o, s in zip(outs, out_shape)]


def _rows(n_tiles: int, width: int, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((n_tiles, width), dtype)


def _reorder_call(kernel, args, key_dtype, val_dtype, t: int, *, interpret):
    """Run a fused postscan+reorder kernel: outputs (keys_r, vals_r?, pos_r,
    perm), each ``(L, t)``; ``vals_r`` is None when ``val_dtype`` is."""
    n_tiles = args[0].shape[0]
    out_shape = [_rows(n_tiles, t, key_dtype)]
    if val_dtype is not None:
        out_shape.append(_rows(n_tiles, t, val_dtype))
    out_shape += [_rows(n_tiles, t), _rows(n_tiles, t)]
    out = _tiled_call(kernel, args, out_shape, interpret=interpret)
    if val_dtype is not None:
        return tuple(out)
    keys_r, pos_r, perm = out
    return keys_r, None, pos_r, perm


def _pad_g(g: Array, width: int, m_pad: int) -> Array:
    return jnp.zeros((g.shape[0], m_pad), g.dtype).at[:, :width].set(g)


# ---------------------------------------------------------------------------
# Kernel 1: per-tile histograms (the prescan direct solve)
# ---------------------------------------------------------------------------

def _histogram_kernel(ids_ref, hist_ref, *, m_pad: int):
    ids = ids_ref[0, :]
    one_hot = _one_hot(ids, m_pad)
    hist_ref[0, :] = one_hot.sum(axis=0).astype(jnp.int32)


def tile_histograms_pallas(ids_tiled: Array, num_buckets: int, *, interpret: bool = True) -> Array:
    """(L, T) int32 ids -> (L, m) int32 histograms."""
    m_pad = _pad_lanes(num_buckets)
    (out,) = _tiled_call(
        functools.partial(_histogram_kernel, m_pad=m_pad), [ids_tiled],
        [_rows(ids_tiled.shape[0], m_pad)], interpret=interpret,
    )
    return out[:, :num_buckets]


# ---------------------------------------------------------------------------
# Kernel 2: per-tile final positions (the DMS postscan direct solve)
# ---------------------------------------------------------------------------

def _positions_kernel(ids_ref, g_ref, pos_ref, *, m_pad: int):
    pos_ref[0, :] = dense_positions_body(ids_ref[0, :], g_ref[0, :], m_pad)


def tile_positions_pallas(
    ids_tiled: Array, g: Array, num_buckets: int, *, interpret: bool = True
) -> Array:
    """(L, T) ids + (L, m) bases -> (L, T) destinations (paper eq. (2))."""
    m_pad = _pad_lanes(num_buckets)
    (pos,) = _tiled_call(
        functools.partial(_positions_kernel, m_pad=m_pad),
        [ids_tiled, _pad_g(g, num_buckets, m_pad)], [_rows(*ids_tiled.shape)],
        interpret=interpret,
    )
    return pos


# ---------------------------------------------------------------------------
# Kernel 3 (THE fused WMS/BMS postscan): one one-hot/cumsum evaluation per
# tile yields local ranks, global destinations, and the bucket-major reorder
# of keys, values and destinations (paper §4.5 + §4.7 in one VMEM pass).
# ---------------------------------------------------------------------------

def _fused_postscan_kernel(*refs, m_pad: int, has_values: bool):
    if has_values:
        (ids_ref, g_ref, keys_ref, vals_ref,
         keys_out_ref, vals_out_ref, pos_out_ref, perm_out_ref) = refs
    else:
        ids_ref, g_ref, keys_ref, keys_out_ref, pos_out_ref, perm_out_ref = refs
        vals_ref = vals_out_ref = None

    keys_r, vals_r, pos_r, gpos = fused_postscan_body(
        ids_ref[0, :], g_ref[0, :], keys_ref[0, :],
        vals_ref[0, :] if has_values else None, m_pad,
    )
    keys_out_ref[0, :] = keys_r
    pos_out_ref[0, :] = pos_r
    perm_out_ref[0, :] = gpos                               # element-ordered perm
    if has_values:
        vals_out_ref[0, :] = vals_r


def fused_postscan_reorder_pallas(
    ids_tiled: Array,
    g: Array,
    keys_tiled: Array,
    values_tiled: Optional[Array],
    num_buckets: int,
    *,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """Fused postscan+reorder: (L,T) ids, (L,m) bases, (L,T) keys [+values]
    -> (keys_r, values_r, positions_r, perm), the first three bucket-major
    within each tile and ``perm`` in original element order.

    ``positions_r[l, j]`` is the GLOBAL destination of the reordered element
    at tile slot ``j`` — the caller's scatter is the only remaining data
    movement (contiguous per-bucket runs; paper §4.7 coalescing).
    ``perm[l, i]`` is the global destination of INPUT element i (eq. (2)) —
    a free byproduct of the same one-hot/cumsum evaluation.
    """
    m_pad = _pad_lanes(num_buckets)
    has_values = values_tiled is not None
    args = [ids_tiled, _pad_g(g, num_buckets, m_pad), keys_tiled] + (
        [values_tiled] if has_values else [])
    return _reorder_call(
        functools.partial(_fused_postscan_kernel, m_pad=m_pad, has_values=has_values),
        args, keys_tiled.dtype, values_tiled.dtype if has_values else None,
        ids_tiled.shape[1], interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Segmented kernels: the segment id rides THROUGH the one-hot/cumsum pass as
# the high part of the combined bucket id cid = seg*m + bucket (DESIGN.md §9).
# ---------------------------------------------------------------------------

def _seg_histogram_kernel(ids_ref, seg_ref, hist_ref, *, m: int, m_pad: int):
    cid = ids_ref[0, :] + seg_ref[0, :] * m                 # in-register combine
    hist_ref[0, :] = _one_hot(cid, m_pad).sum(axis=0).astype(jnp.int32)


def seg_tile_histograms_pallas(
    ids_tiled: Array, seg_tiled: Array, num_buckets: int, num_segments: int,
    *, interpret: bool = True,
) -> Array:
    """(L, T) bucket ids + (L, T) segment ids -> (L, s*m) combined histograms."""
    m_eff = num_buckets * num_segments
    m_pad = _pad_lanes(m_eff)
    (out,) = _tiled_call(
        functools.partial(_seg_histogram_kernel, m=num_buckets, m_pad=m_pad),
        [ids_tiled, seg_tiled], [_rows(ids_tiled.shape[0], m_pad)],
        interpret=interpret,
    )
    return out[:, :m_eff]


def _seg_positions_kernel(ids_ref, seg_ref, g_ref, pos_ref, *, m: int, m_pad: int):
    cid = ids_ref[0, :] + seg_ref[0, :] * m
    pos_ref[0, :] = dense_positions_body(cid, g_ref[0, :], m_pad)


def seg_tile_positions_pallas(
    ids_tiled: Array, seg_tiled: Array, g: Array, num_buckets: int, num_segments: int,
    *, interpret: bool = True,
) -> Array:
    """Segmented DMS postscan: combined (seg, bucket) destinations, eq. (2)."""
    m_eff = num_buckets * num_segments
    m_pad = _pad_lanes(m_eff)
    (pos,) = _tiled_call(
        functools.partial(_seg_positions_kernel, m=num_buckets, m_pad=m_pad),
        [ids_tiled, seg_tiled, _pad_g(g, m_eff, m_pad)],
        [_rows(*ids_tiled.shape)], interpret=interpret,
    )
    return pos


def _seg_fused_postscan_kernel(*refs, m: int, m_pad: int, has_values: bool):
    if has_values:
        (ids_ref, seg_ref, g_ref, keys_ref, vals_ref,
         keys_out_ref, vals_out_ref, pos_out_ref, perm_out_ref) = refs
    else:
        (ids_ref, seg_ref, g_ref, keys_ref,
         keys_out_ref, pos_out_ref, perm_out_ref) = refs
        vals_ref = vals_out_ref = None

    cid = ids_ref[0, :] + seg_ref[0, :] * m                 # in-register combine
    keys_r, vals_r, pos_r, gpos = fused_postscan_body(
        cid, g_ref[0, :], keys_ref[0, :],
        vals_ref[0, :] if has_values else None, m_pad,
    )
    keys_out_ref[0, :] = keys_r
    pos_out_ref[0, :] = pos_r
    perm_out_ref[0, :] = gpos
    if has_values:
        vals_out_ref[0, :] = vals_r


def seg_fused_postscan_reorder_pallas(
    ids_tiled: Array,
    seg_tiled: Array,
    g: Array,
    keys_tiled: Array,
    values_tiled: Optional[Array],
    num_buckets: int,
    num_segments: int,
    *,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """Segmented fused postscan+reorder: per-tile (segment, bucket)-major
    reorder + global destinations from ONE one-hot/cumsum evaluation over the
    combined id. Output contract matches :func:`fused_postscan_reorder_pallas`
    with the bucket axis widened to ``s*m``."""
    m_eff = num_buckets * num_segments
    m_pad = _pad_lanes(m_eff)
    has_values = values_tiled is not None
    args = [ids_tiled, seg_tiled, _pad_g(g, m_eff, m_pad), keys_tiled] + (
        [values_tiled] if has_values else [])
    return _reorder_call(
        functools.partial(
            _seg_fused_postscan_kernel, m=num_buckets, m_pad=m_pad, has_values=has_values
        ),
        args, keys_tiled.dtype, values_tiled.dtype if has_values else None,
        ids_tiled.shape[1], interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused-label kernels (DESIGN.md §11): bucket ids computed IN-REGISTER from a
# declarative BucketSpec — the generic form of the radix kernels. ``spec`` is
# a static kernel parameter (hashable, so the jit'd wrappers cache across
# equal spec instances); ``spec.emit`` is plain vectorized jnp traced into
# the kernel body. No label strip enters or leaves the kernel.
# ---------------------------------------------------------------------------

def _spec_hist_kernel(keys_ref, hist_ref, *, spec, m_pad: int):
    ids = spec.emit_in_kernel(keys_ref[0, :])               # in-register labels
    hist_ref[0, :] = _one_hot(ids, m_pad).sum(axis=0).astype(jnp.int32)


def spec_tile_histograms_pallas(
    keys_tiled: Array, spec, *, interpret: bool = True
) -> Array:
    """(L, T) keys -> (L, m) per-tile histograms; labels fused in-kernel."""
    m = spec.num_buckets
    m_pad = _pad_lanes(m)
    (out,) = _tiled_call(
        functools.partial(_spec_hist_kernel, spec=spec, m_pad=m_pad),
        [keys_tiled], [_rows(keys_tiled.shape[0], m_pad)], interpret=interpret,
    )
    return out[:, :m]


def _spec_positions_kernel(keys_ref, g_ref, pos_ref, *, spec, m_pad: int):
    ids = spec.emit_in_kernel(keys_ref[0, :])
    pos_ref[0, :] = dense_positions_body(ids, g_ref[0, :], m_pad)


def spec_tile_positions_pallas(
    keys_tiled: Array, g: Array, spec, *, interpret: bool = True
) -> Array:
    """Fused-label DMS postscan: (L, T) keys + (L, m) bases -> (L, T) dests."""
    m = spec.num_buckets
    m_pad = _pad_lanes(m)
    (pos,) = _tiled_call(
        functools.partial(_spec_positions_kernel, spec=spec, m_pad=m_pad),
        [keys_tiled, _pad_g(g, m, m_pad)], [_rows(*keys_tiled.shape)],
        interpret=interpret,
    )
    return pos


def _spec_ids_kernel(keys_ref, ids_ref, *, spec):
    ids_ref[0, :] = spec.emit_in_kernel(keys_ref[0, :]).astype(jnp.int32)


def spec_bucket_ids_pallas(
    keys_tiled: Array, spec, *, interpret: bool = True
) -> Array:
    """(L, T) keys -> (L, T) int32 bucket ids: ``spec.emit_in_kernel``
    evaluated per tile. The generic materialized-label entry point — any
    declarative BucketSpec, same plan/tile machinery as every other kernel
    (replaces the seed-era fixed-even-spec kernel in histogram_tile.py)."""
    (ids,) = _tiled_call(
        functools.partial(_spec_ids_kernel, spec=spec), [keys_tiled],
        [_rows(*keys_tiled.shape)], interpret=interpret,
    )
    return ids


def _spec_fused_postscan_kernel(*refs, spec, m_pad: int, has_values: bool):
    if has_values:
        (keys_ref, g_ref, vals_ref,
         keys_out_ref, vals_out_ref, pos_out_ref, perm_out_ref) = refs
    else:
        keys_ref, g_ref, keys_out_ref, pos_out_ref, perm_out_ref = refs
        vals_ref = vals_out_ref = None

    keys = keys_ref[0, :]
    ids = spec.emit_in_kernel(keys)                         # in-register labels
    keys_r, vals_r, pos_r, gpos = fused_postscan_body(
        ids, g_ref[0, :], keys, vals_ref[0, :] if has_values else None, m_pad
    )
    keys_out_ref[0, :] = keys_r
    pos_out_ref[0, :] = pos_r
    perm_out_ref[0, :] = gpos                               # element-ordered perm
    if has_values:
        vals_out_ref[0, :] = vals_r


def spec_fused_postscan_reorder_pallas(
    keys_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    spec,
    *,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """Fused-label WMS/BMS postscan: contract of
    :func:`fused_postscan_reorder_pallas` with the label strip replaced by
    in-kernel ``spec.emit`` evaluation."""
    m = spec.num_buckets
    m_pad = _pad_lanes(m)
    has_values = values_tiled is not None
    args = [keys_tiled, _pad_g(g, m, m_pad)] + ([values_tiled] if has_values else [])
    return _reorder_call(
        functools.partial(
            _spec_fused_postscan_kernel, spec=spec, m_pad=m_pad, has_values=has_values
        ),
        args, keys_tiled.dtype, values_tiled.dtype if has_values else None,
        keys_tiled.shape[1], interpret=interpret,
    )


# -- segmented fused-label kernels: cid = seg*m + spec.emit(keys), both parts
# computed in-register (DESIGN.md §9 x §11).

def _seg_spec_hist_kernel(keys_ref, seg_ref, hist_ref, *, spec, m_pad: int):
    cid = spec.emit_in_kernel(keys_ref[0, :]) + seg_ref[0, :] * spec.num_buckets
    hist_ref[0, :] = _one_hot(cid, m_pad).sum(axis=0).astype(jnp.int32)


def seg_spec_tile_histograms_pallas(
    keys_tiled: Array, seg_tiled: Array, spec, num_segments: int,
    *, interpret: bool = True,
) -> Array:
    """(L, T) keys + (L, T) segment ids -> (L, s*m) combined histograms."""
    m_eff = spec.num_buckets * num_segments
    m_pad = _pad_lanes(m_eff)
    (out,) = _tiled_call(
        functools.partial(_seg_spec_hist_kernel, spec=spec, m_pad=m_pad),
        [keys_tiled, seg_tiled], [_rows(keys_tiled.shape[0], m_pad)],
        interpret=interpret,
    )
    return out[:, :m_eff]


def _seg_spec_positions_kernel(keys_ref, seg_ref, g_ref, pos_ref, *, spec, m_pad: int):
    cid = spec.emit_in_kernel(keys_ref[0, :]) + seg_ref[0, :] * spec.num_buckets
    pos_ref[0, :] = dense_positions_body(cid, g_ref[0, :], m_pad)


def seg_spec_tile_positions_pallas(
    keys_tiled: Array, seg_tiled: Array, g: Array, spec, num_segments: int,
    *, interpret: bool = True,
) -> Array:
    """Segmented fused-label DMS postscan: (seg, bucket) dests, eq. (2)."""
    m_eff = spec.num_buckets * num_segments
    m_pad = _pad_lanes(m_eff)
    (pos,) = _tiled_call(
        functools.partial(_seg_spec_positions_kernel, spec=spec, m_pad=m_pad),
        [keys_tiled, seg_tiled, _pad_g(g, m_eff, m_pad)],
        [_rows(*keys_tiled.shape)], interpret=interpret,
    )
    return pos


def _seg_spec_fused_postscan_kernel(*refs, spec, m_pad: int, has_values: bool):
    if has_values:
        (keys_ref, seg_ref, g_ref, vals_ref,
         keys_out_ref, vals_out_ref, pos_out_ref, perm_out_ref) = refs
    else:
        keys_ref, seg_ref, g_ref, keys_out_ref, pos_out_ref, perm_out_ref = refs
        vals_ref = vals_out_ref = None

    keys = keys_ref[0, :]
    cid = spec.emit_in_kernel(keys) + seg_ref[0, :] * spec.num_buckets
    keys_r, vals_r, pos_r, gpos = fused_postscan_body(
        cid, g_ref[0, :], keys, vals_ref[0, :] if has_values else None, m_pad
    )
    keys_out_ref[0, :] = keys_r
    pos_out_ref[0, :] = pos_r
    perm_out_ref[0, :] = gpos
    if has_values:
        vals_out_ref[0, :] = vals_r


def seg_spec_fused_postscan_reorder_pallas(
    keys_tiled: Array,
    seg_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    spec,
    num_segments: int,
    *,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """Segmented fused-label postscan+reorder: contract of
    :func:`seg_fused_postscan_reorder_pallas` with in-kernel labels."""
    m_eff = spec.num_buckets * num_segments
    m_pad = _pad_lanes(m_eff)
    has_values = values_tiled is not None
    args = [keys_tiled, seg_tiled, _pad_g(g, m_eff, m_pad)] + (
        [values_tiled] if has_values else [])
    return _reorder_call(
        functools.partial(
            _seg_spec_fused_postscan_kernel, spec=spec, m_pad=m_pad,
            has_values=has_values,
        ),
        args, keys_tiled.dtype, values_tiled.dtype if has_values else None,
        keys_tiled.shape[1], interpret=interpret,
    )


# ---------------------------------------------------------------------------
# PACKED kernel family (DESIGN.md §12): subword bucket counters packed k per
# uint32 word + two-level (subtile -> tile) ranking, replacing the T×m
# one-hot/cumsum of every kernel above. ONE generic kernel per pipeline
# stage covers all four dense shapes — {ids strip | in-register spec labels}
# × {flat | segmented} — selected by static flags, so the packed family has
# exactly three entry points (histograms / positions / fused reorder).
# ---------------------------------------------------------------------------

def _packed_ids(x, seg_ref, *, spec, m: int):
    """The combined bucket id strip of one tile, computed in-register:
    ``spec.emit_in_kernel`` when label-fused, plus the segment high part."""
    ids = spec.emit_in_kernel(x) if spec is not None else x
    if seg_ref is not None:
        ids = ids + seg_ref[0, :] * m
    return ids


def _packed_hist_kernel(*refs, spec, m: int, has_seg: bool, layout):
    if has_seg:
        x_ref, seg_ref, hist_ref = refs
    else:
        (x_ref, hist_ref), seg_ref = refs, None
    ids = _packed_ids(x_ref[0, :], seg_ref, spec=spec, m=m)
    hist_ref[0, :] = packed_counts(ids, layout)


def packed_tile_histograms_pallas(
    tiled: Array,
    num_buckets: int,
    *,
    spec=None,
    seg_tiled: Optional[Array] = None,
    num_segments: int = 1,
    bits: Optional[int] = None,
    subtile: Optional[int] = None,
    interpret: bool = True,
) -> Array:
    """Packed prescan: (L, T) ids (or keys when ``spec`` fuses labels)
    [+ (L, T) segment ids] -> (L, s*m) int32 histograms. Contract of
    :func:`tile_histograms_pallas` / its seg/spec variants, one entry."""
    n_tiles, t = tiled.shape
    m = spec.num_buckets if spec is not None else num_buckets
    m_eff = m * num_segments
    layout = packed_layout(t, m_eff, **_layout_kw(bits, subtile))
    has_seg = seg_tiled is not None
    (hist,) = _tiled_call(
        functools.partial(
            _packed_hist_kernel, spec=spec, m=m, has_seg=has_seg, layout=layout
        ),
        [tiled, seg_tiled] if has_seg else [tiled], [_rows(n_tiles, m_eff)],
        interpret=interpret,
    )
    return hist


def _packed_positions_kernel(*refs, spec, m: int, has_seg: bool, layout,
                             oblivious: bool):
    if has_seg:
        x_ref, seg_ref, g_ref, pos_ref = refs
    else:
        (x_ref, g_ref, pos_ref), seg_ref = refs, None
    ids = _packed_ids(x_ref[0, :], seg_ref, spec=spec, m=m)
    pos_ref[0, :] = packed_positions_body(
        ids, g_ref[0, :], layout, oblivious=oblivious
    )


def packed_tile_positions_pallas(
    tiled: Array,
    g: Array,
    num_buckets: int,
    *,
    spec=None,
    seg_tiled: Optional[Array] = None,
    num_segments: int = 1,
    bits: Optional[int] = None,
    subtile: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Array:
    """Packed DMS postscan: (L, T) ids/keys + (L, s*m) bases -> (L, T)
    destinations (paper eq. (2)); two-level packed rank, no one-hot.
    ``oblivious`` (default) traces the gather-free rank-plane body that
    lowers under Mosaic; ``oblivious=False`` keeps the gather form."""
    n_tiles, t = tiled.shape
    m = spec.num_buckets if spec is not None else num_buckets
    m_eff = m * num_segments
    layout = packed_layout(t, m_eff, rank16=oblivious, **_layout_kw(bits, subtile))
    has_seg = seg_tiled is not None
    (pos,) = _tiled_call(
        functools.partial(
            _packed_positions_kernel, spec=spec, m=m, has_seg=has_seg,
            layout=layout, oblivious=oblivious,
        ),
        [tiled, seg_tiled, g] if has_seg else [tiled, g], [_rows(n_tiles, t)],
        interpret=interpret,
    )
    return pos


def _packed_fused_kernel(
    *refs, spec, m: int, has_seg: bool, has_keys: bool, has_values: bool,
    layout, oblivious: bool,
):
    refs = list(refs)
    x_ref = refs.pop(0)
    seg_ref = refs.pop(0) if has_seg else None
    g_ref = refs.pop(0)
    keys_ref = refs.pop(0) if has_keys else x_ref
    vals_ref = refs.pop(0) if has_values else None
    if has_values:
        keys_out_ref, vals_out_ref, pos_out_ref, perm_out_ref = refs
    else:
        (keys_out_ref, pos_out_ref, perm_out_ref), vals_out_ref = refs, None

    ids = _packed_ids(x_ref[0, :], seg_ref, spec=spec, m=m)
    keys_r, vals_r, pos_r, gpos = packed_postscan_body(
        ids, g_ref[0, :], keys_ref[0, :],
        vals_ref[0, :] if has_values else None, layout, oblivious=oblivious,
    )
    keys_out_ref[0, :] = keys_r
    pos_out_ref[0, :] = pos_r
    perm_out_ref[0, :] = gpos                               # element-ordered perm
    if has_values:
        vals_out_ref[0, :] = vals_r


def packed_fused_postscan_reorder_pallas(
    tiled: Array,
    g: Array,
    keys_tiled: Optional[Array] = None,
    values_tiled: Optional[Array] = None,
    *,
    spec=None,
    num_buckets: Optional[int] = None,
    seg_tiled: Optional[Array] = None,
    num_segments: int = 1,
    bits: Optional[int] = None,
    subtile: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """Packed WMS/BMS postscan+reorder: the output contract of
    :func:`fused_postscan_reorder_pallas` (and its seg/spec variants) from
    ONE two-level packed-rank evaluation per tile.

    ``tiled`` is the id strip (with ``keys_tiled`` alongside) or, when
    ``spec`` is given, the key strip itself (labels in-register; no separate
    keys input). ``oblivious`` (default) traces the gather-free select/
    permutation-matmul body (DESIGN.md §15); ``oblivious=False`` keeps the
    gather/scatter form."""
    n_tiles, t = tiled.shape
    m = spec.num_buckets if spec is not None else num_buckets
    m_eff = m * num_segments
    layout = packed_layout(t, m_eff, rank16=oblivious, **_layout_kw(bits, subtile))
    has_seg = seg_tiled is not None
    has_keys = keys_tiled is not None
    has_values = values_tiled is not None
    key_src = keys_tiled if has_keys else tiled
    args = ([tiled] + ([seg_tiled] if has_seg else []) + [g]
            + ([keys_tiled] if has_keys else [])
            + ([values_tiled] if has_values else []))
    return _reorder_call(
        functools.partial(
            _packed_fused_kernel, spec=spec, m=m, has_seg=has_seg,
            has_keys=has_keys, has_values=has_values, layout=layout,
            oblivious=oblivious,
        ),
        args, key_src.dtype, values_tiled.dtype if has_values else None, t,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# FUSED TWO-DIGIT kernels (DESIGN.md §13): one grid program runs TWO radix
# digit passes per VMEM residency — digit-d solve, in-VMEM reorder, digit-
# (d+1) solve on the reordered tile — and emits the combined 2r-bit pair
# histogram, so the caller scatters through HBM once per digit PAIR. The
# pair digit is a static BitfieldSpec (shift, 2r) with ``split`` marking the
# low-digit width; ``family`` selects the m-wide stage-solve family (dense
# one-hot or packed subword counters). Inherently label-fused: the kernels
# take KEY strips only. Like the packed family, three generic entry points
# cover {flat | segmented} × {keys-only | key-value}.
# ---------------------------------------------------------------------------

def _fused2_hist_kernel(*refs, shift: int, bits: int, num_segments: int,
                        has_seg: bool, oblivious: bool):
    if has_seg:
        keys_ref, seg_ref, hist_ref = refs
    else:
        (keys_ref, hist_ref), seg_ref = refs, None
    seg = seg_ref[0, :] if has_seg else None
    if oblivious:                           # the (R, C) table, stored 2-D
        hist_ref[...] = fused2_counts_2d(
            keys_ref[0, :], shift, bits, seg=seg, num_segments=num_segments)
        return
    hist_ref[0, :] = fused2_counts_body(
        keys_ref[0, :], shift, bits, seg=seg, num_segments=num_segments)


def fused2_tile_histograms_pallas(
    keys_tiled: Array,
    spec,
    *,
    seg_tiled: Optional[Array] = None,
    num_segments: int = 1,
    oblivious: bool = True,
    interpret: bool = True,
) -> Array:
    """Fused2 prescan: (L, T) keys [+ (L, T) segment ids] -> (L, s·m²)
    combined pair histograms. ``oblivious`` (default) contracts two
    half-width one-hots on the MXU (Mosaic-lowerable); ``oblivious=False``
    keeps the O(T) in-kernel scatter-add. The m²-wide one-hot never exists
    on either path."""
    n_tiles = keys_tiled.shape[0]
    has_seg = seg_tiled is not None
    (hist,) = _tiled_call(
        functools.partial(
            _fused2_hist_kernel, shift=spec.shift, bits=spec.bits,
            num_segments=num_segments, has_seg=has_seg, oblivious=oblivious,
        ),
        [keys_tiled, seg_tiled] if has_seg else [keys_tiled],
        [_pair_table(n_tiles, spec, num_segments, oblivious)],
        interpret=interpret,
    )
    return hist.reshape(n_tiles, -1)


def _fused2_positions_kernel(*refs, shift: int, split: int, bits: int,
                             num_segments: int, family: str,
                             sub_bits: Optional[int], has_seg: bool,
                             oblivious: bool):
    if has_seg:
        keys_ref, seg_ref, g_ref, pos_ref = refs
    else:
        (keys_ref, g_ref, pos_ref), seg_ref = refs, None
    pos_ref[0, :] = fused2_positions_body(
        keys_ref[0, :], g_ref[...] if oblivious else g_ref[0, :], shift, split, bits,
        seg=seg_ref[0, :] if has_seg else None, num_segments=num_segments,
        family=family, sub_bits=sub_bits, oblivious=oblivious,
    )


def fused2_tile_positions_pallas(
    keys_tiled: Array,
    g: Array,
    spec,
    split: int,
    *,
    seg_tiled: Optional[Array] = None,
    num_segments: int = 1,
    family: str = "onehot",
    sub_bits: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Array:
    """Fused2 DMS postscan: (L, T) keys + (L, s·m²) pair bases -> (L, T)
    element-ordered global pair destinations (paper eq. (2) over the
    combined digit)."""
    has_seg = seg_tiled is not None
    (pos,) = _tiled_call(
        functools.partial(
            _fused2_positions_kernel, shift=spec.shift, split=split,
            bits=spec.bits, num_segments=num_segments, family=family,
            sub_bits=sub_bits, has_seg=has_seg, oblivious=oblivious,
        ),
        [keys_tiled] + ([seg_tiled] if has_seg else [])
        + [_pair_g(g, spec, num_segments, oblivious)],
        [_rows(*keys_tiled.shape)], interpret=interpret,
    )
    return pos


def _fused2_fused_kernel(*refs, shift: int, split: int, bits: int,
                         num_segments: int, family: str,
                         sub_bits: Optional[int], has_seg: bool,
                         has_values: bool, oblivious: bool):
    refs = list(refs)
    keys_ref = refs.pop(0)
    seg_ref = refs.pop(0) if has_seg else None
    g_ref = refs.pop(0)
    vals_ref = refs.pop(0) if has_values else None
    if has_values:
        keys_out_ref, vals_out_ref, pos_out_ref, perm_out_ref = refs
    else:
        (keys_out_ref, pos_out_ref, perm_out_ref), vals_out_ref = refs, None

    keys_r, vals_r, pos_r, gpos = fused2_postscan_body(
        keys_ref[0, :], g_ref[...] if oblivious else g_ref[0, :],
        vals_ref[0, :] if has_values else None, shift, split, bits,
        seg=seg_ref[0, :] if has_seg else None, num_segments=num_segments,
        family=family, sub_bits=sub_bits, oblivious=oblivious,
    )
    keys_out_ref[0, :] = keys_r
    pos_out_ref[0, :] = pos_r
    perm_out_ref[0, :] = gpos                               # element-ordered perm
    if has_values:
        vals_out_ref[0, :] = vals_r


def fused2_fused_postscan_reorder_pallas(
    keys_tiled: Array,
    g: Array,
    values_tiled: Optional[Array] = None,
    *,
    spec,
    split: int,
    seg_tiled: Optional[Array] = None,
    num_segments: int = 1,
    family: str = "onehot",
    sub_bits: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE fused two-digit postscan+reorder: output contract of
    :func:`fused_postscan_reorder_pallas` over the combined pair digit —
    both digit solves and the intermediate reorder stay in VMEM; the
    caller's single scatter per PAIR is the only HBM round trip.
    ``oblivious`` (default) traces the gather-free stage-permutation body
    of DESIGN.md §15; ``oblivious=False`` keeps the gather/scatter form."""
    has_seg = seg_tiled is not None
    has_values = values_tiled is not None
    args = ([keys_tiled] + ([seg_tiled] if has_seg else [])
            + [_pair_g(g, spec, num_segments, oblivious)]
            + ([values_tiled] if has_values else []))
    return _reorder_call(
        functools.partial(
            _fused2_fused_kernel, shift=spec.shift, split=split,
            bits=spec.bits, num_segments=num_segments, family=family,
            sub_bits=sub_bits, has_seg=has_seg, has_values=has_values,
            oblivious=oblivious,
        ),
        args, keys_tiled.dtype, values_tiled.dtype if has_values else None,
        keys_tiled.shape[1], interpret=interpret,
    )


def _pair_table(n_tiles: int, spec, num_segments: int, oblivious: bool):
    """Per-tile shape of a fused2 pair table (histogram or G): the oblivious
    kernels keep it as its (R, C) factoring (:func:`fused2_counts_2d`)."""
    if not oblivious:
        return _rows(n_tiles, spec.num_buckets * num_segments)
    _, n_rows, n_cols = _pair_hist2d_shape(spec.bits, num_segments)
    return jax.ShapeDtypeStruct((n_tiles, n_rows, n_cols), jnp.int32)


def _pair_g(g: Array, spec, num_segments: int, oblivious: bool) -> Array:
    return g.reshape(_pair_table(g.shape[0], spec, num_segments, oblivious).shape)


def _layout_kw(bits: Optional[int], subtile: Optional[int]) -> dict:
    kw = {}
    if bits is not None:
        kw["bits"] = bits
    if subtile is not None:
        kw["subtile"] = subtile
    return kw
