"""Shared building blocks for the multisplit Pallas kernels (DESIGN.md §4).

Every kernel in this package is built from a small set of VMEM-resident
primitives, so they live in one module instead of being re-derived per file.

The DENSE one-hot family (DESIGN.md §2):

* :func:`one_hot_f32`   — the paper's binary matrix ``H̄`` (§4.5) built with a
  broadcasted iota compare (no gather, VPU-friendly).
* :func:`cumsum_mxu`    — inclusive column scan as a lower-triangular ones
  matmul: maps the warp-scan of paper Alg. 3 onto the MXU systolic array.
* :func:`exclusive_starts_mxu` — exclusive scan of a histogram row via a
  *strictly* lower-triangular matmul (bucket start offsets).
* :func:`permute_matmul_32` — apply a within-tile permutation to 32-bit words
  as TWO half-word one-hot matmuls (16-bit halves keep fp32 accumulation
  exact) — MXU work instead of a serialized scatter (paper §4.7 reorder).

All integer payloads are carried through fp32 matmuls in exact range
(< 2^24 per half-word / count), which every kernel test checks bit-exactly.

The PACKED subword-counter family (DESIGN.md §12, paper §4.3): the dense
family's per-tile work and VMEM scale as ``T × m`` because every element
materializes a full one-hot row.  The packed family instead privatizes
``k = 32 / bits`` bucket counters per ``uint32`` word — the vectorized
analogue of the paper's packed shared-memory counters — and ranks elements
with a TWO-LEVEL hierarchy: an inclusive scan of packed words inside
``subtile``-row blocks (counts bounded by ``2^bits − 1``, the overflow
guard of :func:`packed_layout`), then one small ``S × m`` exclusive scan
across the blocks.  The scan matrix shrinks from ``T × m`` f32 words to
``T × ⌈m/k⌉`` uint32 words and the quadratic cumsum matmul disappears, so
per-key work is ~flat in the bucket count up to m = 256.  Shared entry
points: :func:`packed_layout`, :func:`packed_local_offsets`,
:func:`packed_counts`, :func:`packed_positions_body`,
:func:`packed_postscan_body` — the SAME jnp bodies are traced inside the
Pallas kernels and vmapped by the jnp emulation backends, which is what
makes the two families bitwise-comparable oracles of each other.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray


def pad_lanes(m: int) -> int:
    """Pad the bucket axis to a multiple of 128 lanes (min one full lane)."""
    return max(128, ((m + 127) // 128) * 128)


def one_hot_f32(ids: Array, m_pad: int) -> Array:
    """(T,) int32 -> (T, m_pad) f32 one-hot via broadcasted iota (no gather)."""
    t = ids.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, m_pad), 1)
    return (cols == ids[:, None]).astype(jnp.float32)


def cumsum_mxu(x: Array) -> Array:
    """Inclusive column cumsum as a lower-triangular matmul (MXU-native)."""
    t = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    tril = (rows >= cols).astype(jnp.float32)
    return jax.lax.dot(tril, x, precision=jax.lax.Precision.HIGHEST)


def exclusive_starts_mxu(hist: Array) -> Array:
    """(m,) f32 histogram -> (m,) exclusive prefix (bucket start offsets)."""
    m = hist.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    strict_tril = (rows > cols).astype(jnp.float32)
    return matvec_f32(strict_tril, hist)


def matvec_f32(mat: Array, v: Array) -> Array:
    """``mat @ v`` for an (N, K) matrix and a (K,) f32 vector, as ONE
    lane-dense (1, K) x (N, K)^T MXU contraction giving a (1, N) row. The
    vector rides as a row, never as a (K, 1) column: Mosaic's column
    extraction of a narrow matmul result misplaces rows on the chip."""
    return jax.lax.dot_general(
        v[None, :], mat, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )[0]


def matvec_32(mat: Array, x: Array) -> Array:
    """``mat @ x`` for a 0/1 (N, K) matrix with at most one 1 per row and a
    (K,) vector of 32-bit words (int32/uint32/float32, bit-exact): the words
    split into 16-bit halves (f32-exact), both halves ride ONE (2, K) x
    (N, K)^T contraction as lane-dense rows, and the halves reassemble."""
    xi = jax.lax.bitcast_convert_type(x, jnp.uint32)
    which = jax.lax.broadcasted_iota(jnp.int32, (2, xi.shape[0]), 0)
    halves = jnp.where(which == 0,
                       _half_to_f32(xi & jnp.uint32(0xFFFF))[None, :],
                       _half_to_f32(xi >> jnp.uint32(16))[None, :])   # (2, K)
    moved = jax.lax.dot_general(
        halves, mat, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )                                                       # (2, N)
    lo = _f32_to_u32(moved[0])
    hi = _f32_to_u32(moved[1])
    return jax.lax.bitcast_convert_type(lo | (hi << jnp.uint32(16)), x.dtype)


def permutation_matrix(dest: Array) -> Array:
    """(T,) int32 destinations -> (T, T) f32 P with P[j, i] = (dest_i == j)."""
    t = dest.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    return (rows == dest[None, :]).astype(jnp.float32)


def _half_to_f32(u: Array) -> Array:
    """uint32 half-word (< 2^16) -> f32, through int32: Mosaic lowers no
    uint32 -> float32 cast, and the value is exact in both."""
    return u.astype(jnp.int32).astype(jnp.float32)


def _f32_to_u32(x: Array) -> Array:
    """Exact small non-negative f32 -> uint32, through int32 (Mosaic lowers
    no float32 -> uint32 cast)."""
    return x.astype(jnp.int32).astype(jnp.uint32)


def _word_sum(x: Array, axis: int, cumulative: bool = False) -> Array:
    """Sum (or inclusive cumsum) of 32-bit words along ``axis``. uint32
    words are carried as int32: Mosaic reduces no unsigned integers, and
    two's-complement addition wraps to the same bits."""
    if x.dtype != jnp.uint32:
        return jnp.cumsum(x, axis=axis) if cumulative else x.sum(axis=axis)
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    r = jnp.cumsum(xi, axis=axis) if cumulative else xi.sum(axis=axis)
    return jax.lax.bitcast_convert_type(r, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("axis", "segment"))
def roll_cumsum(x: Array, axis: int = 0, segment: Optional[int] = None) -> Array:
    """Inclusive prefix sum of integer words along ``axis``, restarting
    every ``segment`` entries: a Hillis-Steele scan of ``pltpu.roll``
    shifts and masked adds, ⌈log2 segment⌉ steps. The kernel form of
    ``jnp.cumsum``, which Pallas TPU does not lower; integer-exact, and
    uint32 words wrap through int32 as in :func:`_word_sum`. Jitted:
    ``pltpu.roll`` has no eager evaluation rule, and inside a kernel the
    jit inlines."""
    if x.ndim == 1:
        return roll_cumsum(x[None, :], axis=1, segment=segment)[0]
    seg = x.shape[axis] if segment is None else segment
    unsigned = x.dtype == jnp.uint32
    xi = jax.lax.bitcast_convert_type(x, jnp.int32) if unsigned else x
    pos = jax.lax.broadcasted_iota(jnp.int32, xi.shape, axis)
    if seg != x.shape[axis]:
        pos = pos % seg
    d = 1
    while d < seg:
        xi = xi + jnp.where(pos >= d, pltpu.roll(xi, d, axis), 0)
        d *= 2
    return jax.lax.bitcast_convert_type(xi, jnp.uint32) if unsigned else xi


def _cumsum(x: Array, axis: int, oblivious: bool) -> Array:
    """``jnp.cumsum`` on the gather form, :func:`roll_cumsum` on the
    oblivious (compiled-kernel) form; bitwise equal."""
    return roll_cumsum(x, axis) if oblivious else jnp.cumsum(x, axis=axis)


def select_columns(rows: Array, col: Array) -> Array:
    """``rows[i, col[i]]`` WITHOUT a gather: broadcasted-iota compare along
    the static column axis + masked sum (exactly one term survives per row).
    The oblivious, Mosaic-lowerable form of ``take_along_axis(rows, col, 1)``
    — the TPU analogue of the paper's ballot/shuffle lane exchange."""
    t, w = rows.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, w), 1)
    zero = jnp.zeros((), rows.dtype)
    return _word_sum(jnp.where(cols == col[:, None], rows, zero), axis=1)


def pick_row_32(one_hot: Array, row: Array) -> Array:
    """One-hot pick ``row[ids]`` of FULL-RANGE 32-bit entries: the (T, m) f32
    one-hot times the row split into 16-bit halves, one MXU matmul, exact
    (each half < 2^16 ≤ 2^24; see :func:`matvec_32`)."""
    return matvec_32(one_hot, row)


def rank_plane_pack16(rows: Array) -> Array:
    """(S, m) int32 ranks (each < 2^16, guarded by ``packed_layout``) ->
    (S, ceil(m/2)) uint32 LANE-PACKED RANK PLANES: two bucket carries per
    int32 lane, even bucket in the low half-word. Halves the select width
    of the packed family's level-2 carry lookup on the oblivious path."""
    m = rows.shape[1]
    h = -(-m // 2)
    b = jax.lax.broadcasted_iota(jnp.int32, (m, h), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (m, h), 1)
    x = rows.astype(jnp.float32)

    def half(off):
        # column 2j+off of ``rows`` as column j: a 0/1 selection matmul,
        # exact for ranks < 2^16 (a lane reshape does not lower in Mosaic)
        sel = (b == 2 * j + off).astype(jnp.float32)
        return _f32_to_u32(
            jax.lax.dot(x, sel, precision=jax.lax.Precision.HIGHEST))

    return half(0) | (half(1) << jnp.uint32(16))


def dense_global_positions(one_hot: Array, local: Array, g_row: Array) -> Array:
    """Paper eq. (2) on the dense path: ``g_row[ids] + local`` as int32.
    ``g_row`` holds global bucket offsets, which reach ``n`` and so pass
    2^24 at the paper's sizes: the pick goes through
    :func:`pick_row_32`'s 16-bit halves and the add is integer, both exact
    where an f32 pick-and-add would round."""
    return pick_row_32(one_hot, g_row.astype(jnp.int32)) + local.astype(jnp.int32)


def dense_positions_body(ids: Array, g_row: Array, m_pad: int) -> Array:
    """THE dense DMS postscan (no reorder): global destinations, eq. (2)."""
    one_hot = one_hot_f32(ids, m_pad)
    incl = cumsum_mxu(one_hot)
    local = ((incl - 1.0) * one_hot).sum(axis=1)            # rank within bucket
    return dense_global_positions(one_hot, local, g_row)


def fused_postscan_body(ids, g_row, keys, vals, m_pad: int):
    """THE fused postscan+reorder math, shared by the generic and radix
    kernels (they differ only in where ``ids`` comes from): ONE
    one-hot/cumsum evaluation yields local ranks, the tile histogram and
    bucket starts, the within-tile destination, the global destination
    (paper eq. (2)), and the bucket-major permutation of keys/values/
    positions. Returns (keys_r, vals_r_or_None, pos_r, gpos)."""
    t = ids.shape[0]
    one_hot = one_hot_f32(ids, m_pad)                       # THE one-hot (T, m)
    incl = cumsum_mxu(one_hot)                              # THE cumsum
    local = ((incl - 1.0) * one_hot).sum(axis=1)            # (T,) in-bucket rank
    hist = incl[t - 1, :]                                   # (m,) tile histogram
    starts = exclusive_starts_mxu(hist)                     # (m,) tile bucket starts
    dest = (matvec_f32(one_hot, starts) + local).astype(jnp.int32)  # within-tile destination
    gpos = dense_global_positions(one_hot, local, g_row)    # eq. (2)
    perm = permutation_matrix(dest)
    keys_r = permute_matmul_32(perm, keys)
    pos_r = permute_matmul_32(perm, gpos)
    vals_r = permute_matmul_32(perm, vals) if vals is not None else None
    return keys_r, vals_r, pos_r, gpos


# ---------------------------------------------------------------------------
# Packed subword counters (DESIGN.md §12; paper §4.3's privatized packed
# counters, emulated with shift/mask vector ops).
# ---------------------------------------------------------------------------

DEFAULT_PACKED_BITS = 8      # counter width: k = 32/bits counters per word


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Resolved packed-counter geometry for one tile shape (hashable, so it
    rides as a static kernel/jit parameter like a BucketSpec).

    ``bits`` is the subword counter width, ``k = 32 // bits`` the counters
    per uint32 word, ``w = ceil(m_eff / k)`` the packed words per element
    row, ``subtile`` the level-1 scan span (counts inside one subtile are
    bounded by ``subtile`` ≤ ``2^bits − 1``: the no-overflow invariant), and
    ``n_sub = ceil(tile / subtile)`` the level-2 height."""

    tile: int
    m_eff: int
    bits: int
    k: int
    w: int
    subtile: int
    n_sub: int

    @property
    def lane_mask(self):
        return jnp.uint32((1 << self.bits) - 1)


def packed_layout(
    tile: int,
    m_eff: int,
    bits: int = DEFAULT_PACKED_BITS,
    subtile: Optional[int] = None,
    rank16: bool = False,
) -> PackedLayout:
    """Resolve (and GUARD) the packed-counter geometry for one tile.

    Raises ``ValueError`` for any (tile, bits, subtile) combination that
    could overflow a subword counter — a subtile taller than ``2^bits − 1``
    rows could put more than ``2^bits − 1`` equal bucket ids into one
    counter lane (the adversarial all-one-bucket input), silently wrapping
    it.  The auto subtile is the largest power of two that is provably safe
    (and ≤ 128, one VPU sublane block).

    ``rank16=True`` additionally guards the OBLIVIOUS path's 16-bit
    lane-packed rank planes (:func:`rank_plane_pack16`): two level-2 carries
    share one int32 lane, and a carry can reach ``tile`` on the adversarial
    all-one-bucket input, so tiles taller than ``2^16 − 1`` rows would
    silently wrap a half-word rank."""
    if tile < 1:
        raise ValueError(f"packed layout needs tile >= 1, got {tile}")
    if rank16 and tile > 0xFFFF:
        raise ValueError(
            f"tile={tile} overflows the 16-bit lane-packed rank planes: a "
            f"level-2 carry can reach {tile} > 65535 and two ranks share "
            f"one int32 lane on the oblivious path. Use tile <= 65535 (or "
            f"the gather form, oblivious=False)."
        )
    if m_eff < 1:
        raise ValueError(f"packed layout needs m_eff >= 1, got {m_eff}")
    if bits not in (1, 2, 4, 8, 16):
        raise ValueError(
            f"bits-per-counter must divide 32 and be <= 16, got {bits}"
        )
    cap = (1 << bits) - 1                     # max exact count per lane
    if subtile is None:
        subtile = 1
        while subtile * 2 <= min(tile, cap, 128):
            subtile *= 2
    if subtile < 1:
        raise ValueError(f"subtile must be >= 1, got {subtile}")
    if subtile > cap:
        raise ValueError(
            f"subtile={subtile} overflows {bits}-bit packed counters: a "
            f"single-bucket subtile reaches count {subtile} > {cap} "
            f"(= 2^{bits} - 1). Use a shorter subtile or wider counters."
        )
    k = 32 // bits
    return PackedLayout(
        tile=tile, m_eff=m_eff, bits=bits, k=k, w=-(-m_eff // k),
        subtile=subtile, n_sub=-(-tile // subtile),
    )


def _packed_pad_ids(ids: Array, layout: PackedLayout) -> Tuple[Array, int]:
    """Pad the id strip to a whole number of subtiles with bucket m_eff−1
    (tail pads never change earlier elements' ranks; callers slice/adjust)."""
    t = ids.shape[0]
    n_pad = (-t) % layout.subtile
    if n_pad:
        ids = jnp.concatenate(
            [ids, jnp.full((n_pad,), layout.m_eff - 1, ids.dtype)]
        )
    return ids, n_pad


def packed_encode(ids: Array, layout: PackedLayout) -> Array:
    """(T,) int32 ids -> (T, w) uint32 packed one-hot: element i contributes
    ``1 << (bits * (id mod k))`` to word ``id div k`` (shift/mask emulation
    of the paper's per-warp packed counter update)."""
    t = ids.shape[0]
    q = (ids // layout.k).astype(jnp.int32)
    shift = jnp.uint32(layout.bits) * (ids % layout.k).astype(jnp.uint32)
    unit = jnp.uint32(1) << shift
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, layout.w), 1)
    return jnp.where(cols == q[:, None], unit[:, None], jnp.uint32(0))


def packed_unpack(
    packed_rows: Array, layout: PackedLayout, oblivious: bool = False
) -> Array:
    """(R, w) uint32 packed counters -> (R, m_eff) int32 counts.

    ``oblivious=True`` (the compiled kernel form) spreads each counter lane
    to its bucket column with one 0/1 expansion matmul per lane (counts
    < 2^16, f32-exact) instead of a (w, k) -> w·k lane reshape, which
    Mosaic does not lower."""
    if oblivious:
        j = jax.lax.broadcasted_iota(jnp.int32, (layout.w, layout.m_eff), 0)
        b = jax.lax.broadcasted_iota(jnp.int32, (layout.w, layout.m_eff), 1)
        counts = None
        for lane in range(layout.k):
            c = _half_to_f32(
                (packed_rows >> jnp.uint32(layout.bits * lane)) & layout.lane_mask)
            spread = (b == j * layout.k + lane).astype(jnp.float32)
            part = jax.lax.dot(c, spread, precision=jax.lax.Precision.HIGHEST)
            counts = part if counts is None else counts + part
        return counts.astype(jnp.int32)
    shifts = jnp.uint32(layout.bits) * jnp.arange(layout.k, dtype=jnp.uint32)
    lanes = (packed_rows[:, :, None] >> shifts[None, None, :]) & layout.lane_mask
    return lanes.reshape(packed_rows.shape[0], layout.w * layout.k)[
        :, : layout.m_eff
    ].astype(jnp.int32)


def _packed_state(ids: Array, layout: PackedLayout, oblivious: bool = False):
    """The shared two-level solve: (rank_incl, sub_hist, excl_sub).

    ``rank_incl`` is the 1-based stable rank of each element within its
    (subtile, bucket) cell; ``sub_hist`` the (S, m_eff) per-subtile
    histograms; ``excl_sub`` their exclusive scan over subtiles (the level-2
    carry each element adds to reach its within-tile rank). ``oblivious``
    swaps the per-element packed-word lookup from a gather to a masked
    w-wide lane select (Mosaic-lowerable)."""
    ids, _ = _packed_pad_ids(ids, layout)
    t_pad = ids.shape[0]
    q = (ids // layout.k).astype(jnp.int32)
    shift = jnp.uint32(layout.bits) * (ids % layout.k).astype(jnp.uint32)
    contrib = packed_encode(ids, layout)
    # level 1: inclusive scan of packed words inside each subtile — counts
    # stay <= subtile <= 2^bits - 1, so lanes never carry into each other.
    contrib3 = contrib.reshape(layout.n_sub, layout.subtile, layout.w)
    if oblivious:
        incl = roll_cumsum(contrib, 0, segment=layout.subtile)
        word = select_columns(incl, q)
        sub_tot = _word_sum(contrib3, axis=1)   # == the scan's last rows
    else:
        incl3 = _word_sum(contrib3, axis=1, cumulative=True)
        incl = incl3.reshape(t_pad, layout.w)
        word = jnp.take_along_axis(incl, q[:, None], axis=1)[:, 0]
        sub_tot = incl3[:, -1, :]
    rank_incl = ((word >> shift) & layout.lane_mask).astype(jnp.int32)
    # level 2: unpack ONLY the S subtile totals and scan those — S*m work
    # instead of the dense family's T*m.
    sub_hist = packed_unpack(sub_tot, layout, oblivious)    # (S, m_eff)
    excl_sub = _cumsum(sub_hist, 0, oblivious) - sub_hist
    return rank_incl, sub_hist, excl_sub


def _drop_pad_count(hist: Array, m_eff: int, n_pad: int) -> Array:
    """Subtract the tail-pad count from the LAST bucket without a scatter:
    an iota compare + subtract, bitwise equal to ``hist.at[m-1].add(-n)``."""
    if not n_pad:
        return hist
    last = (jnp.arange(m_eff, dtype=jnp.int32) == m_eff - 1)
    return hist - n_pad * last.astype(hist.dtype)


def packed_local_offsets(
    ids: Array, layout: PackedLayout, oblivious: bool = False
) -> Tuple[Array, Array]:
    """Packed-counter analogue of the dense one-hot local solve: (stable
    0-based in-bucket rank within the tile, tile histogram), bitwise equal
    to ``tile_local_offsets(ids, m_eff)``.

    ``oblivious=True`` (the compiled kernel path) replaces the level-2 carry
    gather ``excl_sub[sub, id]`` with 16-BIT LANE-PACKED RANK PLANES: the
    (S, m_eff) carries are packed two-per-int32-lane, each subtile's plane
    row is broadcast statically to its rows, and the element's word is a
    masked ⌈m/2⌉-wide select — half the select width of an unpacked lookup.
    Exactness requires every carry < 2^16 (tile ≤ 65535; guarded here and
    in ``packed_layout(rank16=True)``)."""
    t = ids.shape[0]
    rank_incl, sub_hist, excl_sub = _packed_state(ids, layout, oblivious=oblivious)
    if oblivious:
        if layout.tile > 0xFFFF:
            raise ValueError(
                f"packed oblivious path: tile={layout.tile} level-2 carries "
                f"do not fit the 16-bit lane-packed rank planes (max 65535); "
                f"resolve the layout with packed_layout(rank16=True)"
            )
        ids_p, _ = _packed_pad_ids(ids, layout)
        planes = rank_plane_pack16(excl_sub)                # (S, ceil(m/2))
        w16 = planes.shape[1]
        per_row = jnp.broadcast_to(
            planes[:, None, :], (layout.n_sub, layout.subtile, w16)
        ).reshape(layout.n_sub * layout.subtile, w16)
        word = select_columns(per_row, (ids_p // 2).astype(jnp.int32))
        carry = (
            (word >> (jnp.uint32(16) * (ids_p % 2).astype(jnp.uint32)))
            & jnp.uint32(0xFFFF)
        ).astype(jnp.int32)
        local = carry[:t] + rank_incl[:t] - 1
    else:
        sub_idx = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)[:, 0] // layout.subtile
        local = excl_sub[sub_idx, ids] + rank_incl[:t] - 1
    hist = sub_hist.sum(axis=0)
    n_pad = layout.n_sub * layout.subtile - t
    hist = _drop_pad_count(hist, layout.m_eff, n_pad)       # drop internal pads
    return local.astype(jnp.int32), hist.astype(jnp.int32)


def packed_counts(ids: Array, layout: PackedLayout) -> Array:
    """Histogram-only form: per-subtile packed SUMS (no scan) + one unpack.
    Bitwise equal to the dense tile histogram (and gather-free as-is)."""
    t = ids.shape[0]
    ids, n_pad = _packed_pad_ids(ids, layout)
    contrib = packed_encode(ids, layout)
    sub_tot = _word_sum(
        contrib.reshape(layout.n_sub, layout.subtile, layout.w), axis=1
    )
    hist = packed_unpack(sub_tot, layout, oblivious=True).sum(axis=0)
    hist = _drop_pad_count(hist, layout.m_eff, n_pad)
    return hist.astype(jnp.int32)


def packed_positions_body(
    ids: Array, g_row: Array, layout: PackedLayout, oblivious: bool = False
) -> Array:
    """Packed DMS postscan: global destinations, paper eq. (2)."""
    local, _ = packed_local_offsets(ids, layout, oblivious=oblivious)
    if oblivious:
        g_pick = pick_row_32(one_hot_f32(ids, layout.m_eff),
                             g_row.astype(jnp.int32))
        return (g_pick + local).astype(jnp.int32)
    return (g_row.astype(jnp.int32)[ids] + local).astype(jnp.int32)


def packed_postscan_body(
    ids, g_row, keys, vals, layout: PackedLayout, oblivious: bool = False
):
    """THE packed fused postscan+reorder: same contract as
    :func:`fused_postscan_body` — (keys_r, vals_r_or_None, pos_r, gpos) with
    the first three bucket-major within the tile — built on the two-level
    packed rank. The gather form scatters in-tile; the oblivious form picks
    starts/G via ONE m_eff-wide one-hot (16-bit-half matmuls, exact for full
    32-bit globals) and reorders through permutation matmuls — every step a
    select or an MXU contraction, nothing Mosaic refuses to lower."""
    local, hist = packed_local_offsets(ids, layout, oblivious=oblivious)
    starts = (_cumsum(hist, 0, oblivious) - hist).astype(jnp.int32)
    if oblivious:
        oh = one_hot_f32(ids, layout.m_eff)
        dest = (pick_row_32(oh, starts) + local).astype(jnp.int32)
        gpos = (pick_row_32(oh, g_row.astype(jnp.int32)) + local).astype(jnp.int32)
        perm = permutation_matrix(dest)
        keys_r = permute_matmul_32(perm, keys)
        pos_r = permute_matmul_32(perm, gpos)
        vals_r = permute_matmul_32(perm, vals) if vals is not None else None
        return keys_r, vals_r, pos_r, gpos
    dest = (starts[ids] + local).astype(jnp.int32)          # within-tile destination
    gpos = (g_row.astype(jnp.int32)[ids] + local).astype(jnp.int32)  # eq. (2)
    keys_r = jnp.zeros_like(keys).at[dest].set(keys)
    pos_r = jnp.zeros_like(gpos).at[dest].set(gpos)
    vals_r = jnp.zeros_like(vals).at[dest].set(vals) if vals is not None else None
    return keys_r, vals_r, pos_r, gpos


# ---------------------------------------------------------------------------
# Fused two-digit radix bodies (DESIGN.md §13): TWO digit passes per VMEM
# residency. One tile of keys (and values) is loaded once; the digit-d local
# solve reorders the tile IN VMEM, the digit-(d+1) solve then runs on the
# locally-reordered tile, and the emitted histogram covers the combined
# 2r-bit pair digit — so the global scan layer places elements with a SINGLE
# HBM scatter per digit *pair* instead of per digit. Correctness rests on the
# LSD identity: two chained stable passes over digits (lo, hi) equal ONE
# stable pass over the combined bitfield ``hi·2^r_lo + lo`` — the pair is
# just a ``2r``-bit BitfieldSpec at the tile level.
#
# The same identity applies INSIDE the tile, so the postscan body decomposes
# the 2r-bit in-tile solve all the way down to ``_FUSED2_SUB_BITS``-wide
# sub-digit stages (an in-VMEM LSD sweep: stable stage solve + in-VMEM
# reorder per sub-digit, segment id as the most-significant stage). Narrow
# stages keep every solve plane at T×2^sub instead of T×m — measured ~2×
# cheaper than two m-wide stage solves at r=8 and strictly less VMEM; the
# dense direct solve would need a T×m² one-hot, which never exists (the only
# m²-wide objects are histogram/scan ROWS). Every body below carries BOTH
# forms: the gather/scatter form (``oblivious=False``, the vmap oracle and
# the host fast path) and the oblivious select/matmul form
# (``oblivious=True``, the compiled Mosaic path — DESIGN.md §15), bitwise
# identical by construction and property-tested against each other.
# ---------------------------------------------------------------------------

# In-tile sub-digit stage width of the fused2 LSD sweep. 4 bits = 16-wide
# stage solves: measured fastest on the host bench for BOTH families (2-bit
# stages double the stage count, 8-bit stages quadruple the plane width).
_FUSED2_SUB_BITS = 4


def fused2_split_digits(keys: Array, shift: int, bits_lo: int, bits_hi: int):
    """(lo, hi) digit strips of the pair bitfield at ``shift`` — the same
    arithmetic as ``BitfieldSpec.emit`` on each half, so the fused pair is
    bitwise consistent with the two chained single-digit passes."""
    u = keys.astype(jnp.uint32)
    lo = ((u >> jnp.uint32(shift)) & jnp.uint32((1 << bits_lo) - 1)).astype(jnp.int32)
    hi = ((u >> jnp.uint32(shift + bits_lo))
          & jnp.uint32((1 << bits_hi) - 1)).astype(jnp.int32)
    return lo, hi


def _dense_local_offsets(
    ids: Array, m: int, oblivious: bool = False
) -> Tuple[Array, Array]:
    """Dense int32 one-hot/cumsum local solve: (stable in-bucket rank, tile
    histogram). The jnp form shared by the fused2 stage solves. The
    oblivious form reads the element's own cumsum cell with a masked
    one-hot product instead of ``take_along_axis`` (same int32 math)."""
    t = ids.shape[0]
    one_hot = (ids[:, None] == jnp.arange(m, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    incl = _cumsum(one_hot, 0, oblivious)
    if oblivious:
        local = (incl * one_hot).sum(axis=1) - 1
    else:
        local = jnp.take_along_axis(incl, ids[:, None].astype(jnp.int32), axis=1)[:, 0] - 1
    return local.astype(jnp.int32), incl[t - 1].astype(jnp.int32)


def _fused2_stage_local(
    ids: Array, m: int, family: str, oblivious: bool = False
) -> Tuple[Array, Array]:
    """One m-wide stage solve of the fused pair, in the plan's kernel family."""
    if family == "packed":
        lay = packed_layout(ids.shape[0], m, rank16=oblivious)
        return packed_local_offsets(ids, lay, oblivious=oblivious)
    return _dense_local_offsets(ids, m, oblivious=oblivious)


def _pair_hist2d_shape(bits: int, num_segments: int) -> Tuple[int, int, int]:
    """Factor the (segments × pair) histogram axis for the oblivious
    two-level one-hot contraction: ``cg = row · n_cols + col`` with
    ``n_cols = 2^⌈bits/2⌉`` columns (the pair's low half) and
    ``n_rows = segments · 2^(bits−⌈bits/2⌉)`` rows (segment + high half).
    Keeps the one-hot planes at T×(√m²) each instead of T×m²."""
    col_bits = (bits + 1) // 2
    n_cols = 1 << col_bits
    n_rows = (1 << (bits - col_bits)) * num_segments
    return col_bits, n_rows, n_cols


def fused2_counts_body(
    keys: Array,
    shift: int,
    bits: int,
    seg: Optional[Array] = None,
    num_segments: int = 1,
    oblivious: bool = False,
) -> Array:
    """Per-tile histogram over the combined ``bits``-wide pair digit (the
    fused2 prescan). The gather form is an O(T) scatter-add. The oblivious
    form factors the m²·s-wide axis into (row, column) halves and contracts
    the two one-hots on the MXU — ``histᵀ = oh_rowᵀ · oh_col`` — so the
    planes stay T×√m² each and the counts (< 2^24) are f32-exact. Both are
    order-invariant, hence computed on the UN-reordered tile; bitwise equal
    to the histogram the postscan body derives from its cell counts."""
    if oblivious:
        return fused2_counts_2d(keys, shift, bits, seg, num_segments).reshape(-1)
    m2 = 1 << bits
    u = keys.astype(jnp.uint32)
    pair = ((u >> jnp.uint32(shift)) & jnp.uint32(m2 - 1)).astype(jnp.int32)
    cg = pair if seg is None else seg * m2 + pair
    return jnp.zeros((m2 * num_segments,), jnp.int32).at[cg].add(1)


def _hist2d(cg: Array, bits: int, num_segments: int):
    """(row one-hot, column mask, (R, C) int32 cell histogram) of the
    combined cell ids ``cg`` in the :func:`_pair_hist2d_shape` factoring."""
    col_bits, n_rows, n_cols = _pair_hist2d_shape(bits, num_segments)
    col = (cg & (n_cols - 1)).astype(jnp.int32)
    oh_r = one_hot_f32((cg >> col_bits).astype(jnp.int32), n_rows)
    oh_c = one_hot_f32(col, n_cols)
    hist2d = jax.lax.dot(oh_r.T, oh_c, precision=jax.lax.Precision.HIGHEST)
    return oh_r, oh_c > 0, hist2d.astype(jnp.int32)


def fused2_counts_2d(
    keys: Array, shift: int, bits: int, seg: Optional[Array] = None,
    num_segments: int = 1,
) -> Array:
    """The oblivious fused2 prescan as its (R, C) factored table — the
    row-major view of :func:`fused2_counts_body`. Kernels store it 2-D: a
    2-D to 1-D reshape of an m²-wide table does not lower in Mosaic."""
    m2 = 1 << bits
    u = keys.astype(jnp.uint32)
    pair = ((u >> jnp.uint32(shift)) & jnp.uint32(m2 - 1)).astype(jnp.int32)
    cg = pair if seg is None else seg * m2 + pair
    return _hist2d(cg, bits, num_segments)[2]


def _exclusive_scan_2d(h: Array) -> Array:
    """Exclusive prefix sum of an (R, C) table in row-major order, kept
    2-D: the in-row exclusive scan plus the exclusive scan of row totals."""
    in_row = roll_cumsum(h, axis=1) - h
    totals = h.sum(axis=1, keepdims=True)
    return in_row + (roll_cumsum(totals, axis=0) - totals)


def fused2_postscan_body(
    keys: Array,
    g_row: Array,
    vals: Optional[Array],
    shift: int,
    split: int,
    bits: int,
    seg: Optional[Array] = None,
    num_segments: int = 1,
    family: str = "onehot",
    sub_bits: Optional[int] = None,
    oblivious: bool = False,
):
    """THE fused two-digit postscan+reorder: same contract as
    :func:`fused_postscan_body` / :func:`packed_postscan_body` —
    (keys_r, vals_r_or_None, pos_r, gpos), the first three combined-bucket-
    major within the tile — but over the ``bits``-wide PAIR digit.

    ``split`` is the schedule-level boundary between the pair's two logical
    digits (it fixes which two chained passes the pair replaces). By the LSD
    identity the RESULT depends only on the combined stable pass, not on how
    the in-tile solve is decomposed — so the body is free to decompose
    further: an in-VMEM LSD sweep over ``_FUSED2_SUB_BITS``-wide sub-digit
    stages (stable stage solve + in-VMEM reorder per stage, segment id as
    the most-significant stage). Each stage's solve plane is T×2^sub instead
    of T×m — measured ~2× cheaper than two ``split``-wide stage solves at
    r=8 — and after the sweep the tile is already (seg, pair)-bucket-major,
    so the stable in-cell rank is just position minus the cell's tile start.
    The caller's single scatter per pair stays bitwise identical to the two
    chained single-digit scatters it replaces.

    ``oblivious=True`` (the compiled kernel path) removes every in-tile
    gather/scatter, bitwise-identically: stage reorders become permutation
    matmuls (segments ride the permutation instead of being gathered by
    ``seg[idx2]``), the m²·s-wide cell histogram becomes the two-level
    one-hot MXU contraction of :func:`fused2_counts_body`, per-cell
    starts/G lookups become row-matmul × column-select picks in 16-bit
    halves (exact for full 32-bit globals), and the final element-order /
    values permutations apply the ONE tracked source permutation (and its
    transpose) as matmuls.
    """
    t = keys.shape[0]
    del split  # decomposition is sub-digit-wide; result is split-invariant
    # per-shape autotuned stage width (DESIGN.md §14), else the measured
    # global default — the RESULT is sub_bits-invariant (LSD identity),
    # only the stage count / plane width trade-off moves
    sb = sub_bits or _FUSED2_SUB_BITS
    m2 = 1 << bits
    idx = jnp.arange(t, dtype=jnp.int32)
    keys2, idx2 = keys, idx
    seg2 = seg if oblivious else None   # oblivious path carries seg in-order

    def _stage(d, m, keys2, idx2, seg2):
        local, hist = _fused2_stage_local(d, m, family, oblivious=oblivious)
        starts = (_cumsum(hist, 0, oblivious) - hist).astype(jnp.int32)
        if oblivious:
            starts_d = select_columns(jnp.broadcast_to(starts[None, :], (t, m)), d)
            perm = permutation_matrix(starts_d + local)
            keys2 = permute_matmul_32(perm, keys2)
            idx2 = permute_matmul_32(perm, idx2)
            if seg2 is not None:
                seg2 = permute_matmul_32(perm, seg2)
            return keys2, idx2, seg2
        dest = starts[d] + local
        return (jnp.zeros_like(keys2).at[dest].set(keys2),
                jnp.zeros_like(idx2).at[dest].set(idx2), seg2)

    # ---- in-VMEM LSD sweep: sub-digit stages LSB→MSB across the pair bits;
    # values are never moved per stage — idx2 tracks the source slot, so
    # they are picked up once at the end.
    for off in range(0, bits, sb):
        b = min(sb, bits - off)
        m = 1 << b
        d = ((keys2.astype(jnp.uint32) >> jnp.uint32(shift + off))
             & jnp.uint32(m - 1)).astype(jnp.int32)
        keys2, idx2, seg2 = _stage(d, m, keys2, idx2, seg2)
    if seg is not None and num_segments > 1:
        d_seg = seg2 if oblivious else seg[idx2]
        keys2, idx2, seg2 = _stage(d_seg, num_segments, keys2, idx2, seg2)

    # ---- placement: the tile is (seg, pair)-bucket-major, so the stable
    # in-cell rank is position minus the cell's tile start
    pair2 = ((keys2.astype(jnp.uint32) >> jnp.uint32(shift))
             & jnp.uint32(m2 - 1)).astype(jnp.int32)
    if oblivious:
        cg2 = pair2 if seg is None else seg2 * m2 + pair2
        _, n_rows, n_cols = _pair_hist2d_shape(bits, num_segments)
        oh_r, col_mask, hist2d = _hist2d(cg2, bits, num_segments)  # (T,R),(T,C),(R,C)
        starts_t = _exclusive_scan_2d(hist2d)
        # kernels hand G over as its (R, C) table (see fused2_counts_2d)
        g2d = g_row if g_row.ndim == 2 else g_row.reshape(n_rows, n_cols)

        def _pick2d(table):
            # table[cg2] of an (R, C) table without a gather: one-hot row
            # matmul brings the element's (C,) row slice in, a masked column
            # select finishes; 16-bit halves keep full 32-bit values exact.
            u = jax.lax.bitcast_convert_type(table, jnp.uint32)
            lo = jax.lax.dot(oh_r, _half_to_f32(u & jnp.uint32(0xFFFF)),
                             precision=jax.lax.Precision.HIGHEST)
            hi = jax.lax.dot(oh_r, _half_to_f32(u >> jnp.uint32(16)),
                             precision=jax.lax.Precision.HIGHEST)
            lo_s = _f32_to_u32(jnp.where(col_mask, lo, 0.0).sum(axis=1))
            hi_s = _f32_to_u32(jnp.where(col_mask, hi, 0.0).sum(axis=1))
            return jax.lax.bitcast_convert_type(
                lo_s | (hi_s << jnp.uint32(16)), jnp.int32)

        local_c = idx - _pick2d(starts_t)
        gpos2 = (_pick2d(g2d.astype(jnp.int32)) + local_c).astype(jnp.int32)
        q = permutation_matrix(idx2)         # q[j, i] = (idx2_i == j)
        vals_r = permute_matmul_32(q.T, vals) if vals is not None else None
        gpos = permute_matmul_32(q, gpos2)                  # element-ordered perm
        return keys2, vals_r, gpos2, gpos

    cg2 = pair2 if seg is None else seg[idx2] * m2 + pair2
    hist_c = jnp.zeros((m2 * num_segments,), jnp.int32).at[cg2].add(1)
    starts_t = (jnp.cumsum(hist_c) - hist_c).astype(jnp.int32)
    local_c = idx - starts_t[cg2]
    gpos2 = (g_row.astype(jnp.int32)[cg2] + local_c).astype(jnp.int32)

    vals_r = vals[idx2] if vals is not None else None
    gpos = jnp.zeros_like(gpos2).at[idx2].set(gpos2)        # element-ordered perm
    return keys2, vals_r, gpos2, gpos


def fused2_positions_body(
    keys: Array,
    g_row: Array,
    shift: int,
    split: int,
    bits: int,
    seg: Optional[Array] = None,
    num_segments: int = 1,
    family: str = "onehot",
    sub_bits: Optional[int] = None,
    oblivious: bool = False,
) -> Array:
    """Fused2 DMS postscan: global pair destinations in element order —
    the ``gpos`` byproduct of the full body (the in-VMEM reorder is still
    how the combined rank is derived)."""
    return fused2_postscan_body(
        keys, g_row, None, shift, split, bits, seg=seg,
        num_segments=num_segments, family=family, sub_bits=sub_bits,
        oblivious=oblivious,
    )[3]


def fused2_vmem_bytes(
    tile: int, m_lo: int, num_segments: int = 1, family: str = "onehot",
    key_value: bool = False, m_hi: Optional[int] = None,
    sub_bits: Optional[int] = None, oblivious: bool = False,
) -> int:
    """Working-set model of the DOUBLE-RESIDENT fused2 tile, in bytes: ONE
    sub-digit-wide stage solve plane (reused across the LSD sweep's stages —
    width ``min(2^_FUSED2_SUB_BITS, m)``, or ``num_segments`` if wider), the
    reordered keys/index copies living alongside the originals (+ the values
    gather when key-value), and the m²-wide histogram/scan/starts rows. The
    tile heuristic budgets this instead of the single-digit cost when
    ``digits=2`` (DESIGN.md §13) — the gather form grows only ~linearly in T
    with a SMALL constant, which is what lets fused tiles be much larger
    than single-digit ones (and they must be: a pair's G traffic is L·m²
    words, so the pair only profits when L is small).

    ``oblivious=True`` (the kernel backends' compiled-lowerable bodies)
    additionally charges the T×T permutation planes (the stage reorders,
    the tracked source permutation and their iota/compare temporaries: ~7
    f32 words per T² entry, fitted to the v5e compiler's scoped-VMEM
    figures of 2.6 MiB at T=256 and 8.0 MiB at T=512 for a 16-bit pair) and
    the two-level one-hot / pick planes (T×(R + 2C) f32, R·C = m²·s) — the
    quadratic term dominates and pulls the fused2 tile optimum DOWN on
    kernel backends, the opposite shift of the gather form (DESIGN.md
    §15)."""
    m_hi = m_lo if m_hi is None else m_hi
    m2 = m_lo * m_hi
    stage_w = max(min(1 << (sub_bits or _FUSED2_SUB_BITS), max(m_lo, m_hi)),
                  num_segments)
    if family == "packed":
        lay = packed_layout(tile, stage_w)
        solve = 4 * (2 * tile * lay.w + 3 * lay.n_sub * stage_w)
    else:
        solve = 4 * 2 * tile * pad_lanes(stage_w)
    # keys + keys2 + idx2 + digit strip + dest (+ values, values gather)
    resident = 4 * tile * (5 + (2 if key_value else 0))
    pair_rows = 4 * 3 * m2 * num_segments                   # hist / G row / starts
    total = solve + resident + pair_rows
    if oblivious:
        bits = max(1, (m2 - 1).bit_length())
        _, n_rows, n_cols = _pair_hist2d_shape(bits, num_segments)
        total += 4 * (7 * tile * tile + tile * (n_rows + 2 * n_cols))
    return total


def permute_matmul_32(perm: Array, x: Array) -> Array:
    """Permute a (T,) vector of 32-bit words by the (T, T) matrix ``perm``,
    exactly (int32/uint32/float32 payloads; see :func:`matvec_32`)."""
    return matvec_32(perm, x)
