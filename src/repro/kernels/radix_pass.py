"""Fused radix-pass kernels: digit extraction + histogram + postscan+reorder.

One multisplit-sort pass (paper §7.1) needs the bucket identifier
``f_k(u) = (u >> k·r) & (2^r − 1)`` evaluated twice (prescan + postscan).
Fusing the shift/mask into the kernels means the label vector NEVER exists in
HBM — the exact overhead the paper's RB-sort baseline pays (§3.4) and its
multisplit avoids.

Since PR-4 the radix digit is just :class:`~repro.core.identifiers.
BitfieldSpec` and in-kernel label fusion is the GENERIC fused-label
machinery of :mod:`repro.kernels.multisplit_tile` (DESIGN.md §11): every
entry point here is a thin ``BitfieldSpec(shift, bits)`` instantiation of
the corresponding ``spec_*`` kernel, kept under its historical name because
``radix_sort`` predates the general mechanism and tests address these doors
directly.

* ``radix_tile_histograms_pallas``        — prescan: digits + tile histogram.
* ``radix_fused_postscan_reorder_pallas`` — postscan: digits + local ranks +
  global destinations + within-tile digit-major reorder in ONE evaluation.
* ``radix_tile_positions_pallas``         — DMS (no-reorder) postscan.
* ``seg_radix_*``                         — segmented variants: the segment
  id combines with the digit in-register, ``cid = (seg << bits) | digit``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.identifiers import BitfieldSpec
from repro.kernels import multisplit_tile as _mst

Array = jnp.ndarray


def radix_tile_histograms_pallas(
    keys_tiled: Array, shift: int, bits: int, *, interpret: bool = True
) -> Array:
    """(L, T) uint32 keys -> (L, 2^bits) per-tile digit histograms (fused)."""
    return _mst.spec_tile_histograms_pallas(
        keys_tiled, BitfieldSpec(shift, bits), interpret=interpret
    )


def radix_tile_positions_pallas(
    keys_tiled: Array, g: Array, shift: int, bits: int, *, interpret: bool = True
) -> Array:
    """Fused DMS postscan for one radix pass: (L, T) keys + (L, m) bases -> (L, T) dests."""
    return _mst.spec_tile_positions_pallas(
        keys_tiled, g, BitfieldSpec(shift, bits), interpret=interpret
    )


def radix_fused_postscan_reorder_pallas(
    keys_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    shift: int,
    bits: int,
    *,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """(L,T) keys + (L,m) bases [+ (L,T) values]
    -> (keys_r, values_r, pos_r, perm), digit-major within each tile
    (contract of :func:`~repro.kernels.multisplit_tile.
    spec_fused_postscan_reorder_pallas`)."""
    return _mst.spec_fused_postscan_reorder_pallas(
        keys_tiled, g, values_tiled, BitfieldSpec(shift, bits), interpret=interpret
    )


def seg_radix_tile_histograms_pallas(
    keys_tiled: Array, seg_tiled: Array, shift: int, bits: int, num_segments: int,
    *, interpret: bool = True,
) -> Array:
    """(L, T) keys + (L, T) segment ids -> (L, s·2^bits) combined histograms."""
    return _mst.seg_spec_tile_histograms_pallas(
        keys_tiled, seg_tiled, BitfieldSpec(shift, bits), num_segments,
        interpret=interpret,
    )


def seg_radix_tile_positions_pallas(
    keys_tiled: Array, seg_tiled: Array, g: Array, shift: int, bits: int,
    num_segments: int, *, interpret: bool = True,
) -> Array:
    """Segmented DMS radix postscan: combined (seg, digit) destinations."""
    return _mst.seg_spec_tile_positions_pallas(
        keys_tiled, seg_tiled, g, BitfieldSpec(shift, bits), num_segments,
        interpret=interpret,
    )


def seg_radix_fused_postscan_reorder_pallas(
    keys_tiled: Array,
    seg_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    shift: int,
    bits: int,
    num_segments: int,
    *,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """Segmented fused radix postscan: (seg, digit)-major within each tile."""
    return _mst.seg_spec_fused_postscan_reorder_pallas(
        keys_tiled, seg_tiled, g, values_tiled, BitfieldSpec(shift, bits),
        num_segments, interpret=interpret,
    )
