"""Jit'd public wrappers around the Pallas kernels.

``interpret=True`` executes kernel bodies in Python on CPU;
``interpret=False`` compiles for TPU via Mosaic. Since the oblivious-body
PR (DESIGN.md §15) every kernel body is gather/scatter-free by default, so
the compiled path is the NORMAL path on TPU hardware: callers resolve the
flag per backend with :func:`resolve_interpret` (compiled when a TPU is
attached, interpreted otherwise, ``REPRO_INTERPRET`` overriding both). The
wrappers are the only entry points the rest of the framework uses.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.identifiers import EvenSpec
from repro.kernels import multisplit_tile as _mst
from repro.kernels import radix_pass as _radix

Array = jnp.ndarray


@functools.lru_cache(maxsize=1)
def _tpu_available() -> bool:
    # a backend that fails to initialize raises here: it must never read as
    # "no TPU", which would silently select interpret mode
    return any(d.platform == "tpu" for d in jax.devices())


# Unrecognized REPRO_INTERPRET values already warned about (one warning per
# distinct value per process — a typo'd env var must not spam every launch).
_WARNED_INTERPRET: set = set()


def resolve_interpret(compiled: bool) -> bool:
    """The per-backend ``interpret`` flag (DESIGN.md §15).

    ``REPRO_INTERPRET=1`` forces interpret mode everywhere (the debug
    escape hatch); ``REPRO_INTERPRET=0`` forces compiled lowering (CI for
    the Mosaic path on TPU runners). Unset, a ``compiled``-capable backend
    lowers compiled exactly when a TPU is attached — this container has
    none, so the default stays bitwise-identical interpret execution.
    Unrecognized values are treated as unset, with a one-time warning —
    a typo'd ``REPRO_INTERPRET=ture`` silently compiling (or not) is
    exactly the confusion the variable exists to remove."""
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    if env and env not in _WARNED_INTERPRET:
        _WARNED_INTERPRET.add(env)
        import warnings

        warnings.warn(
            f"unrecognized REPRO_INTERPRET value {env!r}; accepted values are "
            f"1/true/yes (force interpret), 0/false/no (force compiled), or "
            f"unset (auto-detect: compiled when a TPU is attached) — "
            f"treating as unset",
            RuntimeWarning, stacklevel=2,
        )
    return not (compiled and _tpu_available())


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def tile_histograms(ids_tiled: Array, num_buckets: int, interpret: bool = True) -> Array:
    return _mst.tile_histograms_pallas(ids_tiled, num_buckets, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def tile_positions(ids_tiled: Array, g: Array, num_buckets: int, interpret: bool = True) -> Array:
    return _mst.tile_positions_pallas(ids_tiled, g, num_buckets, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def fused_postscan_reorder(
    ids_tiled: Array,
    g: Array,
    keys_tiled: Array,
    values_tiled: Optional[Array],
    num_buckets: int,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE fused WMS/BMS postscan entry point (see multisplit_tile)."""
    return _mst.fused_postscan_reorder_pallas(
        ids_tiled, g, keys_tiled, values_tiled, num_buckets, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("shift", "bits", "interpret"))
def radix_fused_postscan_reorder(
    keys_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    shift: int,
    bits: int,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE fused radix postscan entry point: digits never leave the kernel."""
    return _radix.radix_fused_postscan_reorder_pallas(
        keys_tiled, g, values_tiled, shift, bits, interpret=interpret
    )


# -- fused-label entry points (DESIGN.md §11): bucket ids computed in-kernel
# from a hashable BucketSpec. ``spec`` is a STATIC jit argument — equal spec
# instances (value-hashable dataclasses) share one trace/compilation, which
# is what kills the per-identifier-instance retrace of the closure era.

@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def spec_tile_histograms(keys_tiled: Array, spec, interpret: bool = True) -> Array:
    return _mst.spec_tile_histograms_pallas(keys_tiled, spec, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def spec_tile_positions(
    keys_tiled: Array, g: Array, spec, interpret: bool = True
) -> Array:
    return _mst.spec_tile_positions_pallas(keys_tiled, g, spec, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def spec_fused_postscan_reorder(
    keys_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    spec,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE fused-label WMS/BMS postscan entry point (see multisplit_tile)."""
    return _mst.spec_fused_postscan_reorder_pallas(
        keys_tiled, g, values_tiled, spec, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("spec", "num_segments", "interpret"))
def seg_spec_tile_histograms(
    keys_tiled: Array, seg_tiled: Array, spec, num_segments: int,
    interpret: bool = True,
) -> Array:
    return _mst.seg_spec_tile_histograms_pallas(
        keys_tiled, seg_tiled, spec, num_segments, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("spec", "num_segments", "interpret"))
def seg_spec_tile_positions(
    keys_tiled: Array, seg_tiled: Array, g: Array, spec, num_segments: int,
    interpret: bool = True,
) -> Array:
    return _mst.seg_spec_tile_positions_pallas(
        keys_tiled, seg_tiled, g, spec, num_segments, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("spec", "num_segments", "interpret"))
def seg_spec_fused_postscan_reorder(
    keys_tiled: Array,
    seg_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    spec,
    num_segments: int,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE segmented fused-label postscan entry point (labels AND segment id
    combined in-register; see multisplit_tile)."""
    return _mst.seg_spec_fused_postscan_reorder_pallas(
        keys_tiled, seg_tiled, g, values_tiled, spec, num_segments,
        interpret=interpret,
    )


# -- packed-counter family entry points (DESIGN.md §12): ONE wrapper per
# pipeline stage covers {ids strip | fused spec labels} × {flat | segmented}.
# ``spec``/counts/segments/layout knobs are static; equal hashable specs and
# layouts share one trace, exactly like the dense spec wrappers above.

@functools.partial(jax.jit, static_argnames=(
    "num_buckets", "spec", "num_segments", "bits", "subtile", "interpret"))
def packed_tile_histograms(
    tiled: Array,
    seg_tiled: Optional[Array] = None,
    *,
    num_buckets: Optional[int] = None,
    spec=None,
    num_segments: int = 1,
    bits: Optional[int] = None,
    subtile: Optional[int] = None,
    interpret: bool = True,
) -> Array:
    """THE packed prescan entry point (see multisplit_tile)."""
    return _mst.packed_tile_histograms_pallas(
        tiled, num_buckets if spec is None else spec.num_buckets, spec=spec,
        seg_tiled=seg_tiled, num_segments=num_segments, bits=bits,
        subtile=subtile, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=(
    "num_buckets", "spec", "num_segments", "bits", "subtile", "oblivious",
    "interpret"))
def packed_tile_positions(
    tiled: Array,
    g: Array,
    seg_tiled: Optional[Array] = None,
    *,
    num_buckets: Optional[int] = None,
    spec=None,
    num_segments: int = 1,
    bits: Optional[int] = None,
    subtile: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Array:
    """THE packed DMS postscan entry point (see multisplit_tile)."""
    return _mst.packed_tile_positions_pallas(
        tiled, g, num_buckets if spec is None else spec.num_buckets,
        spec=spec, seg_tiled=seg_tiled, num_segments=num_segments, bits=bits,
        subtile=subtile, oblivious=oblivious, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=(
    "num_buckets", "spec", "num_segments", "bits", "subtile", "oblivious",
    "interpret"))
def packed_fused_postscan_reorder(
    tiled: Array,
    g: Array,
    keys_tiled: Optional[Array] = None,
    values_tiled: Optional[Array] = None,
    seg_tiled: Optional[Array] = None,
    *,
    num_buckets: Optional[int] = None,
    spec=None,
    num_segments: int = 1,
    bits: Optional[int] = None,
    subtile: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE packed WMS/BMS postscan+reorder entry point (see multisplit_tile)."""
    return _mst.packed_fused_postscan_reorder_pallas(
        tiled, g, keys_tiled, values_tiled, spec=spec,
        num_buckets=num_buckets, seg_tiled=seg_tiled,
        num_segments=num_segments, bits=bits, subtile=subtile,
        oblivious=oblivious, interpret=interpret,
    )


# -- fused two-digit entry points (DESIGN.md §13): TWO radix digit passes per
# VMEM residency. ``spec`` is the combined 2r-bit pair BitfieldSpec and
# ``split`` the low-digit width — both static, like every pair-schedule knob,
# so all tiles of all pair passes with equal (spec, split, config) share one
# trace. ONE wrapper per stage covers {flat | segmented} × {keys | key-value}.

@functools.partial(jax.jit, static_argnames=(
    "spec", "num_segments", "oblivious", "interpret"))
def fused2_tile_histograms(
    keys_tiled: Array,
    seg_tiled: Optional[Array] = None,
    *,
    spec,
    num_segments: int = 1,
    oblivious: bool = True,
    interpret: bool = True,
) -> Array:
    """THE fused2 prescan entry point (see multisplit_tile)."""
    return _mst.fused2_tile_histograms_pallas(
        keys_tiled, spec, seg_tiled=seg_tiled, num_segments=num_segments,
        oblivious=oblivious, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=(
    "spec", "split", "num_segments", "family", "sub_bits", "oblivious",
    "interpret"))
def fused2_tile_positions(
    keys_tiled: Array,
    g: Array,
    seg_tiled: Optional[Array] = None,
    *,
    spec,
    split: int,
    num_segments: int = 1,
    family: str = "onehot",
    sub_bits: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Array:
    """THE fused2 DMS postscan entry point (see multisplit_tile)."""
    return _mst.fused2_tile_positions_pallas(
        keys_tiled, g, spec, split, seg_tiled=seg_tiled,
        num_segments=num_segments, family=family, sub_bits=sub_bits,
        oblivious=oblivious, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=(
    "spec", "split", "num_segments", "family", "sub_bits", "oblivious",
    "interpret"))
def fused2_fused_postscan_reorder(
    keys_tiled: Array,
    g: Array,
    values_tiled: Optional[Array] = None,
    seg_tiled: Optional[Array] = None,
    *,
    spec,
    split: int,
    num_segments: int = 1,
    family: str = "onehot",
    sub_bits: Optional[int] = None,
    oblivious: bool = True,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE fused two-digit postscan+reorder entry point (see multisplit_tile)."""
    return _mst.fused2_fused_postscan_reorder_pallas(
        keys_tiled, g, values_tiled, spec=spec, split=split,
        seg_tiled=seg_tiled, num_segments=num_segments, family=family,
        sub_bits=sub_bits, oblivious=oblivious, interpret=interpret,
    )


# -- segmented entry points (DESIGN.md §9): segment id rides in-kernel ------

@functools.partial(jax.jit, static_argnames=("num_buckets", "num_segments", "interpret"))
def seg_tile_histograms(
    ids_tiled: Array, seg_tiled: Array, num_buckets: int, num_segments: int,
    interpret: bool = True,
) -> Array:
    return _mst.seg_tile_histograms_pallas(
        ids_tiled, seg_tiled, num_buckets, num_segments, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("num_buckets", "num_segments", "interpret"))
def seg_tile_positions(
    ids_tiled: Array, seg_tiled: Array, g: Array, num_buckets: int, num_segments: int,
    interpret: bool = True,
) -> Array:
    return _mst.seg_tile_positions_pallas(
        ids_tiled, seg_tiled, g, num_buckets, num_segments, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("num_buckets", "num_segments", "interpret"))
def seg_fused_postscan_reorder(
    ids_tiled: Array,
    seg_tiled: Array,
    g: Array,
    keys_tiled: Array,
    values_tiled: Optional[Array],
    num_buckets: int,
    num_segments: int,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE segmented WMS/BMS postscan entry point (see multisplit_tile)."""
    return _mst.seg_fused_postscan_reorder_pallas(
        ids_tiled, seg_tiled, g, keys_tiled, values_tiled, num_buckets,
        num_segments, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("shift", "bits", "num_segments", "interpret"))
def seg_radix_tile_histograms(
    keys_tiled: Array, seg_tiled: Array, shift: int, bits: int, num_segments: int,
    interpret: bool = True,
) -> Array:
    return _radix.seg_radix_tile_histograms_pallas(
        keys_tiled, seg_tiled, shift, bits, num_segments, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("shift", "bits", "num_segments", "interpret"))
def seg_radix_tile_positions(
    keys_tiled: Array, seg_tiled: Array, g: Array, shift: int, bits: int,
    num_segments: int, interpret: bool = True,
) -> Array:
    return _radix.seg_radix_tile_positions_pallas(
        keys_tiled, seg_tiled, g, shift, bits, num_segments, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("shift", "bits", "num_segments", "interpret"))
def seg_radix_fused_postscan_reorder(
    keys_tiled: Array,
    seg_tiled: Array,
    g: Array,
    values_tiled: Optional[Array],
    shift: int,
    bits: int,
    num_segments: int,
    interpret: bool = True,
) -> Tuple[Array, Optional[Array], Array, Array]:
    """THE segmented fused radix postscan entry point (digits never leave
    the kernel; the segment id rides with them)."""
    return _radix.seg_radix_fused_postscan_reorder_pallas(
        keys_tiled, seg_tiled, g, values_tiled, shift, bits, num_segments,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def device_histogram(ids_tiled: Array, num_buckets: int, interpret: bool = True) -> Array:
    """(L, T) ids -> (m,) device-wide histogram: the generic per-tile
    prescan kernel reduced over tiles (replaces the seed-era revisited-block
    kernel in histogram_tile.py — same result, one kernel family)."""
    return _mst.tile_histograms_pallas(
        ids_tiled, num_buckets, interpret=interpret
    ).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def spec_bucket_ids(keys_tiled: Array, spec, interpret: bool = True) -> Array:
    """(L, T) keys -> (L, T) int32 bucket ids for ANY declarative spec
    (the generic materialized-label entry point)."""
    return _mst.spec_bucket_ids_pallas(keys_tiled, spec, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("lo", "hi", "num_buckets", "interpret"))
def even_bucket_ids(
    keys_tiled: Array, lo: float, hi: float, num_buckets: int, interpret: bool = True
) -> Array:
    """Even-bucket identification via the generic spec-ids kernel (the
    fixed-function even kernel of histogram_tile.py, subsumed)."""
    return _mst.spec_bucket_ids_pallas(
        keys_tiled, EvenSpec(float(lo), float(hi), num_buckets),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("shift", "bits", "interpret"))
def radix_tile_histograms(keys_tiled: Array, shift: int, bits: int, interpret: bool = True) -> Array:
    return _radix.radix_tile_histograms_pallas(keys_tiled, shift, bits, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("shift", "bits", "interpret"))
def radix_tile_positions(
    keys_tiled: Array, g: Array, shift: int, bits: int, interpret: bool = True
) -> Array:
    return _radix.radix_tile_positions_pallas(keys_tiled, g, shift, bits, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, causal=True, block_q=256, block_k=256, interpret=True):
    from repro.kernels.flash_attention import flash_attention_pallas

    return flash_attention_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=interpret
    )
