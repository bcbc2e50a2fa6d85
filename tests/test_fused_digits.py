"""Fused two-digit radix passes (DESIGN.md §13): the pairing schedule,
bitwise identity of fused vs chained vs per-pass execution on every backend
and layout, the fused2 stage strings/sweep counts, and the recorded
label-fusion decisions (ISSUE 6).

The whole feature is a COST transform: ``fuse_digits=True`` must never
change a single output bit anywhere — the LSD identity (two chained stable
passes over digits (lo, hi) == one stable pass over the combined
``hi·2^r_lo + lo`` bitfield) is what every equivalence test here pins, on
uniform keys, adversarial all-one-bucket keys, odd/partial bit schedules
(r=7 → 4×7+4, r=5 → 6×5+2), key-only and key-value, flat/batched/segmented.
"""

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.pipeline import (
    RadixPipeline,
    clear_tile_cache,
    fusion_decision,
    get_backend,
    radix_pass_pairs,
    radix_passes,
)
from repro.core.pipeline.radix import MAX_PAIR_BITS
from repro.core.sort import radix_sort, segmented_radix_sort

TILED_BACKENDS = ("vmap", "pallas-interpret")
ALL_BACKENDS = ("reference",) + TILED_BACKENDS


def _keys(n, seed=0, hi=2**32, dtype=np.uint32):
    return jnp.asarray(
        np.random.RandomState(seed % (2**31 - 1)).randint(0, hi, n).astype(dtype)
    )


# ---------------------------------------------------------------------------
# The pairing schedule: greedy adjacent merge with a trailing single
# ---------------------------------------------------------------------------

def test_radix_pass_pairs_even_schedule():
    # r=8 over 32-bit keys: four digits -> two 16-bit pairs
    assert radix_pass_pairs(8, 32) == [(0, 16, 8), (16, 16, 8)]


def test_radix_pass_pairs_trailing_single():
    # r=7: 4x7 + 4 -> two 14-bit pairs + the odd 4-bit digit runs UNPAIRED
    assert radix_pass_pairs(7, 32) == [(0, 14, 7), (14, 14, 7), (28, 4, None)]
    # r=5: 6x5 + 2 -> three 10-bit pairs + an unpaired 2-bit tail
    assert radix_pass_pairs(5, 32) == [
        (0, 10, 5), (10, 10, 5), (20, 10, 5), (30, 2, None)]


def test_radix_pass_pairs_uneven_tail_pair():
    # r=4 over 30-bit keys ends in a 4+2 pair
    assert radix_pass_pairs(4, 30)[-1] == (24, 6, 4)


def test_radix_pass_pairs_width_ceiling():
    # a pair that would exceed max_pair_bits stays two singles
    assert radix_pass_pairs(12, 24) == [(0, 12, None), (12, 12, None)]
    assert radix_pass_pairs(8, 32, max_pair_bits=8) == [
        (s, b, None) for s, b in radix_passes(8, 32)]
    assert MAX_PAIR_BITS == 16


def test_radix_pass_pairs_covers_every_bit_once():
    for r in range(2, 13):
        for kb in (24, 30, 32):
            covered = []
            for shift, bits, split in radix_pass_pairs(r, kb):
                covered.extend(range(shift, shift + bits))
                if split is not None:
                    assert 0 < split < bits
            assert covered == list(range(kb)), (r, kb)


# ---------------------------------------------------------------------------
# Bitwise identity: fused == chained == per-pass, everywhere
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("radix_bits", [8, 7, 5])
def test_fused_bitwise_identical_flat_kv(backend, radix_bits):
    n = 20000 if backend != "reference" else 2500
    keys = _keys(n, seed=radix_bits)
    vals = jnp.arange(n, dtype=jnp.int32)
    kf, vf = radix_sort(keys, vals, radix_bits=radix_bits, backend=backend,
                        fuse_digits=True)
    kc, vc = radix_sort(keys, vals, radix_bits=radix_bits, backend=backend,
                        fuse_digits=False)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(kc))
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vc))
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(keys)[order])
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vals)[order])


@pytest.mark.parametrize("backend", TILED_BACKENDS)
def test_fused_segmented_kv(backend):
    n = 12000
    keys = _keys(n, seed=11)
    vals = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.asarray([0, 7, 7, 900, 11000], jnp.int32)  # empty seg included
    kf, vf = segmented_radix_sort(keys, starts, vals, radix_bits=8,
                                  backend=backend, fuse_digits=True)
    kc, vc = segmented_radix_sort(keys, starts, vals, radix_bits=8,
                                  backend=backend, fuse_digits=False)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(kc))
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vc))


def test_fused_batched_rows_sort_independently():
    keys = _keys(3 * 5000, seed=13).reshape(3, 5000)
    kf, _ = radix_sort(keys, radix_bits=8, backend="vmap", fuse_digits=True)
    kc, _ = radix_sort(keys, radix_bits=8, backend="vmap", fuse_digits=False)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(kc))


def test_fused_adversarial_single_pair_bucket():
    # every key lands in ONE combined pair bucket in every sweep — the
    # in-tile LSD sweep degenerates to identity stages; pads must still
    # sort to the tail (the all-ones sentinel shares no bucket only if the
    # constant differs from it, so test both)
    for const in (0xDEADBEEF, 0xFFFFFFFF):
        ka = jnp.full((9000,), np.uint32(const))
        kf, _ = radix_sort(ka, radix_bits=8, backend="vmap", fuse_digits=True)
        np.testing.assert_array_equal(np.asarray(kf), np.asarray(ka))


@given(
    st.integers(min_value=1, max_value=6000),
    st.integers(min_value=0, max_value=2**31 - 1),
    st.sampled_from([8, 7, 5, 4]),
    st.booleans(),
)
@settings(max_examples=12, deadline=None)
def test_fused_equals_chained_property(n, seed, radix_bits, key_value):
    keys = _keys(n, seed=seed)
    vals = jnp.arange(n, dtype=jnp.int32) if key_value else None
    kf, vf = radix_sort(keys, vals, radix_bits=radix_bits, backend="vmap",
                        fuse_digits=True)
    kc, vc = radix_sort(keys, vals, radix_bits=radix_bits, backend="vmap",
                        fuse_digits=False)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(kc))
    if key_value:
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vc))


# ---------------------------------------------------------------------------
# Schedule/stage introspection: sweeps halve, stage strings mark the pairs
# ---------------------------------------------------------------------------

def test_fused_pipeline_sweep_counts_and_stages():
    p = RadixPipeline(1 << 16, radix_bits=8, backend="vmap", fuse_digits=True)
    assert p.n_passes == 4            # logical digits: schedule-invariant
    assert p.n_sweeps == 2            # executed sweeps: one per pair
    assert p.schedule == [(0, 16, 8), (16, 16, 8)]
    st_ = p.plans[0].stages()
    assert st_[0].startswith("prescan:fused2-pair-")
    assert any(s.startswith("postscan:fused2-pair-reorder-") for s in st_)
    # odd schedule: the r=7 trailing 4-bit digit stays a single sweep
    p7 = RadixPipeline(1 << 16, radix_bits=7, backend="vmap", fuse_digits=True)
    assert p7.n_passes == 5 and p7.n_sweeps == 3
    assert p7.schedule[-1] == (28, 4, None)


def test_fused_flag_is_inert_on_non_fusing_backends():
    # the untiled oracle keeps the single-digit schedule: a pair-wide direct
    # solve would be O(n*m^2) with nothing to save
    p = RadixPipeline(4096, radix_bits=8, backend="reference", fuse_digits=True)
    assert p.n_sweeps == p.n_passes == 4
    assert all(split is None for _, _, split in p.schedule)
    assert not get_backend("reference").fuses_digits
    assert get_backend("vmap").fuses_digits


def test_fused_tile_resolves_large():
    # a pair's G traffic is L*m^2 words: the digits=2 heuristic must grow
    # the tile far past the single-digit base so L stays small
    p = RadixPipeline(1 << 18, radix_bits=8, backend="vmap", fuse_digits=True)
    p1 = RadixPipeline(1 << 18, radix_bits=8, backend="vmap", fuse_digits=False)
    assert p.tile >= 16 * p1.tile


# ---------------------------------------------------------------------------
# Label-fusion decisions (ISSUE 6 satellite): measured threshold + reasons
# ---------------------------------------------------------------------------

def test_label_fusion_threshold_is_recorded_with_reason():
    from repro import ops

    clear_tile_cache()
    keys = _keys(4096, seed=17, hi=2**30)
    ops.multisplit(keys, ops.delta_buckets(256, 2**30), backend="vmap")
    ops.multisplit(keys, ops.delta_buckets(512, 2**30), backend="vmap")
    fused, why = fusion_decision("vmap", "DeltaSpec", 256)
    assert fused and "m_eff=256" in why
    unfused, why512 = fusion_decision("vmap", "DeltaSpec", 512)
    assert not unfused and "re-evaluate" in why512
    # the radix digit NEVER materializes, at any width, on any fusing backend
    ops.radix_sort(keys, radix_bits=8, backend="vmap")
    fused_rx, why_rx = fusion_decision("vmap", "BitfieldSpec", 256)
    assert fused_rx and "shift-and-mask" in why_rx


def test_label_fusion_decision_respects_backend():
    from repro import ops

    clear_tile_cache()
    keys = _keys(4096, seed=19, hi=2**30)
    # kernel backends keep fusing at every width: labels live in-register
    ops.multisplit(keys, ops.delta_buckets(512, 2**30), backend="pallas-interpret")
    fused, why = fusion_decision("pallas-interpret", "DeltaSpec", 512)
    assert fused and "in-register" in why
