"""RadixPipeline: chained LSD digit passes on resident buffers (paper §7.1).

The PR-2 ``radix_sort`` rebuilt the full pipeline front door every pass:
re-resolve the tile, re-pad the keys to a tile multiple, re-tile, run, slice
the pad tail off — ⌈key_bits/r⌉ times. Chaining removes the round trip:

* tiles are resolved ONCE (the widest pass keys the heuristic/autotune
  cache) and every per-pass plan shares them;
* the keys/values buffers are padded ONCE with the all-ones sentinel key —
  its digit is m−1 in EVERY pass, so after each pass's stable scatter the
  pads land back at the tail and the next pass can consume the padded
  buffer as-is (ping-pong: each pass scatters into a fresh buffer that
  becomes the next pass's input; under jit XLA aliases the pair);
* each pass is one :meth:`MultisplitPlan.run_tiled` sweep — prescan, scan,
  postscan, scatter on pre-tiled buffers, no layout stage;
* the pad tail is sliced off ONCE, after the last pass.

Works for flat, batched (``batch=b``: per-row passes, one grid per pass) and
segmented (``segments=s``: the position-keyed ``seg_tiled`` buffer is
computed once — segment membership is invariant across passes) layouts, on
every registered backend. The untiled reference backend simply iterates the
direct solve (it never pads, so there is nothing to chain).
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax.numpy as jnp

from repro.core.pipeline import stages as _st
from repro.core.pipeline.registry import get_backend
from repro.core.pipeline.spec import make_radix_plan
from repro.core.pipeline.tiles import resolve_kernel_family, resolve_tile
from repro.runtime import tracing

Array = jnp.ndarray


def radix_passes(radix_bits: int, key_bits: int) -> List[Tuple[int, int]]:
    """The (shift, bits) schedule of an LSD radix sort; the final pass may
    cover fewer bits (e.g. r=7 over 32-bit keys: 4 passes of 7 + one of 4)."""
    n_pass = math.ceil(key_bits / radix_bits)
    return [
        (k * radix_bits, min(radix_bits, key_bits - k * radix_bits))
        for k in range(n_pass)
    ]


# Pair width ceiling for the fused schedule: a pair's combined digit is the
# scan axis (m = 2^bits), and 16 bits (m = 65536) is where the G matrix and
# pair histograms stop paying for the saved scatter.
MAX_PAIR_BITS = 16

# Ceiling on ONE pair scan table (per-tile histograms, G): L·2^bits·s int32
# words, L the tile count. Kernel tiles stay small (their T×T planes bound
# VMEM), so at n = 2^25 a 16-bit pair on 512-row tiles would need 16 GiB per
# table — more than a v5e chip's HBM. Past this ceiling the sort keeps the
# chained single-digit schedule (same result, DESIGN.md §13).
MAX_PAIR_TABLE_BYTES = 1 << 30


def radix_pass_pairs(
    radix_bits: int, key_bits: int, max_pair_bits: int = MAX_PAIR_BITS
) -> List[Tuple[int, int, Optional[int]]]:
    """The fused-pair schedule (DESIGN.md §13): adjacent single-digit passes
    of :func:`radix_passes` greedily merged into ``(shift, bits, split)``
    entries — ``split`` is the LOW digit's width inside the pair, ``None``
    marks an unpaired single pass (the trailing odd digit, or a pass whose
    pair would exceed ``max_pair_bits``).

    By LSD stability, running the pair as ONE stable pass over the combined
    ``bits``-wide digit is bitwise identical to the two chained passes it
    replaces; e.g. r=8 over 32-bit keys → ``[(0, 16, 8), (16, 16, 8)]``
    (two sweeps instead of four), r=7 → two 14-bit pairs + a single 4-bit
    trailing pass, r=5 → three 10-bit pairs + a single 2-bit pass. Uneven
    trailing pairs (last digit narrower) fuse too: r=4 over 30-bit keys ends
    in ``(24, 6, 4)``.
    """
    passes = radix_passes(radix_bits, key_bits)
    out: List[Tuple[int, int, Optional[int]]] = []
    i = 0
    while i < len(passes):
        if i + 1 < len(passes):
            (s_a, b_a), (_, b_b) = passes[i], passes[i + 1]
            if b_a + b_b <= max_pair_bits:
                out.append((s_a, b_a + b_b, b_a))
                i += 2
                continue
        shift, bits = passes[i]
        out.append((shift, bits, None))
        i += 1
    return out


class RadixPipeline:
    """A resolved ⌈key_bits/r⌉-pass radix sort over one problem shape.

    Build once (tiles resolved, one plan per digit pass), call with concrete
    arrays. Layouts follow the plan layer: flat ``(n,)`` keys, batched
    ``(b, n)`` rows (``batch=b``), or ragged segments over flat keys
    (``segments=s`` + a ``segment_starts`` call argument).
    """

    def __init__(
        self,
        n: int,
        *,
        radix_bits: int = 8,
        key_bits: int = 32,
        method: str = "bms",
        key_value: bool = False,
        backend: str = "vmap",
        tile: Optional[int] = None,
        batch: Optional[int] = None,
        segments: Optional[int] = None,
        family: Optional[str] = None,
        fuse_digits: bool = False,
        sub_bits: Optional[int] = None,
    ):
        self.n = n
        self.key_value = key_value
        self.backend = backend
        self.batch = batch
        self.segments = segments
        self.fuse_digits = fuse_digits
        self.passes = radix_passes(radix_bits, key_bits)
        s = segments or 1
        be = get_backend(backend)
        fused_stage = be.tiled and be.fuses_digits
        fused = fuse_digits and fused_stage
        if fused:
            # Fused-pair schedule (DESIGN.md §13): each pair is ONE sweep
            # over the combined 2r-bit digit, which the tile stage decomposes
            # into two r-wide solves around an in-VMEM reorder (digit_split).
            # Backends without the capability (the untiled reference oracle:
            # no HBM scatter to save, and a pair-wide direct solve would be
            # O(n·m²)) keep the single-digit schedule — fuse_digits changes
            # execution cost only, never the result, on every backend.
            self.schedule = radix_pass_pairs(radix_bits, key_bits)
            shift0, bits0, split0 = self.schedule[0]
            m_eff = (1 << bits0) * s
            stage_m = (1 << (split0 or bits0)) * s
            # digits=2 keys the family decision separately from genuine
            # digits=1 plans of m == stage_m: a fused-pair pin must never
            # re-family a flat plan, or vice versa (regression-tested).
            self.family = resolve_kernel_family(
                n, stage_m, method, backend, family, digits=2,
                key_value=key_value, pair_m=m_eff,
            )
            self.tile = resolve_tile(
                n, m_eff, method, key_value, backend, tile, family=self.family,
                digits=2, stage_m=stage_m,
            )
            n_tiles = -(-n // self.tile) * (batch or 1)
            pair_m = max(1 << b for _, b, _ in self.schedule) * s
            fused = 4 * n_tiles * pair_m <= MAX_PAIR_TABLE_BYTES
        if fused:
            self.plans = tuple(
                make_radix_plan(
                    n, shift, bits, method=method, key_value=key_value,
                    backend=backend, tile=self.tile, batch=batch,
                    segments=segments, family=self.family, digit_split=split,
                    sub_bits=sub_bits,
                )
                for shift, bits, split in self.schedule
            )
        else:
            self.schedule = [(sh, b, None) for sh, b in self.passes]
            # ONE (tile, kernel family) for every pass, keyed by the widest
            # digit (first pass) — narrower final passes reuse them.
            m_eff = (1 << self.passes[0][1]) * s
            self.family = resolve_kernel_family(n, m_eff, method, backend, family)
            self.tile = resolve_tile(
                n, m_eff, method, key_value, backend, tile, family=self.family
            )
            self.plans = tuple(
                make_radix_plan(
                    n, shift, bits, method=method, key_value=key_value,
                    backend=backend, tile=self.tile, batch=batch, segments=segments,
                    family=self.family,
                )
                for shift, bits in self.passes
            )

    @property
    def n_passes(self) -> int:
        """Logical single-digit passes (⌈key_bits/r⌉) — schedule-invariant;
        the number of HBM sweeps actually run is :attr:`n_sweeps`."""
        return len(self.passes)

    @property
    def n_sweeps(self) -> int:
        """Executed {prescan, scan, postscan, scatter} sweeps: one per
        schedule entry — under ``fuse_digits`` a pair counts ONCE."""
        return len(self.plans)

    def __call__(
        self,
        keys: Array,
        values: Optional[Array] = None,
        segment_starts=None,
    ) -> Tuple[Array, Optional[Array]]:
        if (values is not None) != self.key_value:
            raise ValueError(
                f"radix pipeline resolved for key_value={self.key_value} but "
                f"called with values={'present' if values is not None else 'absent'}"
            )
        if not jnp.issubdtype(keys.dtype, jnp.integer):
            # reject BEFORE any pass runs: the BitfieldSpec digit of a float
            # key is a value conversion (not a bit pattern) and the float
            # pad lane has no all-ones digit — the old path corrupted it
            raise TypeError(
                f"radix sort requires integer keys, got {keys.dtype}; "
                f"reinterpret the buffer (e.g. jax.lax.bitcast_convert_type) "
                f"to uint32 first"
            )
        if self.batch is not None:
            return self._call_batched(keys, values)
        n = self.n
        if keys.shape[0] != n:
            raise ValueError(f"radix pipeline resolved for n={n}, got n={keys.shape[0]}")

        seg = None
        if self.segments is not None:
            if segment_starts is None:
                raise ValueError("segmented radix pipeline requires segment_starts")
            seg = jnp.asarray(segment_starts, jnp.int32)
            if seg.shape != (self.segments,):
                raise ValueError(
                    f"pipeline resolved for {self.segments} segments, got "
                    f"segment_starts shape {seg.shape}"
                )
        elif segment_starts is not None:
            raise ValueError("pipeline is not segmented; segment_starts not accepted")

        if n == 0:
            return keys, values

        be = get_backend(self.backend)
        if not be.tiled:
            # the oracle never tiles: iterate the direct solve per pass
            for plan in self.plans:
                res = plan(keys, values, segment_starts=seg)
                keys, values = res.keys, res.values
            return keys, values

        be.check_keys(keys)
        tile = self.tile
        # ---- pad ONCE: sentinel keys sort to the tail in every pass
        with self.plans[0]._stage("layout", -(-n // tile)):
            keys_pad, _ = _st.pad_to_tiles(
                keys, tile, self.plans[0].pad_key(keys.dtype))
            vals_pad = None
            if values is not None:
                vals_pad, _ = _st.pad_to_tiles(values, tile, 0)
            seg_tiled = None
            if seg is not None:
                # position-keyed and pass-invariant: elements never cross
                # segment boundaries, so one seg buffer drives all passes
                seg_ids = _st.segment_ids_from_starts(seg, n)
                seg_p, _ = _st.pad_to_tiles(seg_ids, tile, self.segments - 1)
                seg_tiled = seg_p.reshape(-1, tile)

        # ---- chained passes on resident buffers (reshape views are free).
        # On label-fusing backends each pass's BitfieldSpec digit is computed
        # inside the tile stage (in-register in the kernels) — zero label
        # traffic; only non-fusing backends materialize the digit strip.
        for plan, (shift, bits, _) in zip(self.plans, self.schedule):
            with tracing.span("repro.sort.pass", shift=shift, bits=bits):
                keys_tiled = keys_pad.reshape(-1, tile)
                vals_tiled = (vals_pad.reshape(-1, tile)
                              if vals_pad is not None else None)
                ids_tiled = None
                if not plan.label_fusion(keys_pad):
                    ids_tiled = plan._host_labels(keys_pad).reshape(-1, tile)
                keys_pad, vals_pad, _, _ = plan.run_tiled(
                    keys_tiled, ids_tiled, vals_tiled, seg_tiled
                )

        # ---- slice the pad tail off ONCE
        return keys_pad[:n], (vals_pad[:n] if values is not None else None)

    def _call_batched(
        self, keys: Array, values: Optional[Array]
    ) -> Tuple[Array, Optional[Array]]:
        b, n = self.batch, self.n
        if keys.shape != (b, n):
            raise ValueError(
                f"batched radix pipeline resolved for shape {(b, n)}, got {keys.shape}"
            )
        if n == 0:
            return keys, values

        be = get_backend(self.backend)
        if not be.tiled:
            for plan in self.plans:
                res = plan(keys, values)
                keys, values = res.keys, res.values
            return keys, values

        be.check_keys(keys)
        tile = self.tile
        l_b = -(-n // tile)
        n_row = l_b * tile
        with self.plans[0]._stage("layout", b * l_b):
            keys_pad = _st.pad_rows(keys, n_row, self.plans[0].pad_key(keys.dtype))
            vals_pad = _st.pad_rows(values, n_row, 0) if values is not None else None

        for plan, (shift, bits, _) in zip(self.plans, self.schedule):
            with tracing.span("repro.sort.pass", shift=shift, bits=bits):
                keys_tiled = keys_pad.reshape(b * l_b, tile)
                vals_tiled = (vals_pad.reshape(b * l_b, tile)
                              if vals_pad is not None else None)
                ids_tiled = None
                if not plan.label_fusion(keys_pad):
                    ids_tiled = plan._host_labels(keys_pad).reshape(b * l_b, tile)
                keys_pad, vals_pad, _, _ = plan.run_tiled(
                    keys_tiled, ids_tiled, vals_tiled, rows=b
                )

        return keys_pad[:, :n], (vals_pad[:, :n] if values is not None else None)
