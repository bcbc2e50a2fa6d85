"""Packed-counter kernel family (DESIGN.md §12): overflow safety, bitwise
equivalence with the dense one-hot family, and (tile, family) resolution.

The packed family's correctness argument rests on one invariant — no
subword counter ever exceeds ``2^bits − 1`` inside a level-1 subtile — so
these tests drive exactly the inputs that stress it: adversarial
all-one-bucket strips that max a counter lane out, subtile heights at the
cap, and property-sampled (tile, m, dtype) grids cross-checked bitwise
against the dense family on every backend.
"""

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.identifiers import delta_buckets, from_fn
from repro.core.multisplit import (
    batched_multisplit,
    multisplit,
    multisplit_ref,
    segmented_multisplit,
)
from repro.core.pipeline import (
    FAMILIES,
    clear_tile_cache,
    family_decision,
    family_decisions,
    make_plan,
    packed_tile_local_offsets,
    resolve_kernel_family,
    tile_local_offsets,
)
from repro.core.pipeline import tiles
from repro.core.pipeline.tiles import PACKED_MIN_BUCKETS, _FAMILY_CACHE
from repro.core.sort import radix_sort
from repro.kernels.common import (
    packed_layout,
    packed_local_offsets,
    packed_counts,
)

TILED_BACKENDS = ("vmap", "pallas-interpret")
ALL_BACKENDS = ("reference",) + TILED_BACKENDS


def _keys(n, seed=0, hi=2**30, dtype=np.uint32):
    return jnp.asarray(
        np.random.RandomState(seed % (2**31 - 1)).randint(0, hi, n).astype(dtype)
    )


# ---------------------------------------------------------------------------
# The overflow guard (satellite): packed_layout must reject any
# (tile, bits, subtile) combination that could wrap a subword counter.
# ---------------------------------------------------------------------------

def test_packed_layout_guard_rejects_overflowable_combos():
    # a 512-row subtile can put 512 > 255 equal ids into one 8-bit lane
    with pytest.raises(ValueError, match="overflow"):
        packed_layout(1024, 256, bits=8, subtile=512)
    with pytest.raises(ValueError, match="overflow"):
        packed_layout(1024, 256, bits=4, subtile=16)
    with pytest.raises(ValueError, match="bits-per-counter"):
        packed_layout(1024, 256, bits=5)
    with pytest.raises(ValueError, match="bits-per-counter"):
        packed_layout(1024, 256, bits=32)
    # the cap itself is legal: counts can reach exactly 2^bits - 1
    assert packed_layout(1024, 256, bits=8, subtile=255).subtile == 255
    assert packed_layout(1024, 256, bits=4, subtile=15).subtile == 15


def test_packed_layout_auto_subtile_is_always_safe():
    for bits in (1, 2, 4, 8, 16):
        for tile in (1, 37, 128, 1024, 4096):
            lay = packed_layout(tile, 256, bits=bits)
            assert lay.subtile <= (1 << bits) - 1
            assert lay.subtile <= 128
            assert lay.k * bits == 32
            assert lay.w == -(-256 // lay.k)


def test_packed_counter_saturates_at_cap_without_wrapping():
    """Adversarial all-one-bucket input maxing a subword counter out at
    exactly 2^bits - 1 (= subtile height 255) stays exact."""
    t, m = 510, 7
    ids = jnp.full((t,), m - 1, jnp.int32)
    lay = packed_layout(t, m, bits=8, subtile=255)
    local, hist = packed_local_offsets(ids, lay)
    np.testing.assert_array_equal(np.asarray(local), np.arange(t))
    assert int(hist[m - 1]) == t
    np.testing.assert_array_equal(np.asarray(packed_counts(ids, lay)), np.asarray(hist))


# ---------------------------------------------------------------------------
# Bitwise equivalence: packed == dense local solve (the property the whole
# family rests on), then end-to-end across backends/layouts/dtypes.
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    t=st.sampled_from((128, 192, 256, 510, 1024)),
    m=st.sampled_from((1, 2, 7, 64, 200, 256, 1000)),
    bits=st.sampled_from((4, 8, 16)),
    adversarial=st.booleans(),
    seed=st.integers(min_value=0, max_value=2**20),
)
def test_packed_local_solve_bitwise_equals_dense(t, m, bits, adversarial, seed):
    if adversarial:
        ids = jnp.full((t,), m - 1, jnp.int32)       # maxes one counter lane
    else:
        ids = jnp.asarray(
            np.random.RandomState(seed % (2**31 - 1)).randint(0, m, t, dtype=np.int32)
        )
    ref_local, ref_hist = tile_local_offsets(ids, m)
    lay = packed_layout(t, m, bits=bits)
    local, hist = packed_local_offsets(ids, lay)
    np.testing.assert_array_equal(np.asarray(local), np.asarray(ref_local))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(ref_hist))
    np.testing.assert_array_equal(np.asarray(packed_counts(ids, lay)), np.asarray(ref_hist))
    # the stage-primitive wrapper resolves the same layout
    local2, hist2 = packed_tile_local_offsets(ids, m)
    np.testing.assert_array_equal(np.asarray(local2), np.asarray(ref_local))
    np.testing.assert_array_equal(np.asarray(hist2), np.asarray(ref_hist))


def _assert_equal(out, ref, key_value):
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts))
    np.testing.assert_array_equal(np.asarray(out.bucket_starts), np.asarray(ref.bucket_starts))
    np.testing.assert_array_equal(np.asarray(out.permutation), np.asarray(ref.permutation))
    if key_value:
        np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))


@settings(max_examples=20, deadline=None)
@given(
    n=st.sampled_from((256, 1000, 2048 + 37)),
    m=st.sampled_from((1, 13, 64, 256)),
    method=st.sampled_from(("dms", "wms", "bms")),
    backend=st.sampled_from(ALL_BACKENDS),
    key_value=st.booleans(),
    signed=st.booleans(),
    seed=st.integers(min_value=0, max_value=2**20),
)
def test_packed_family_bitwise_equals_onehot_end_to_end(
    n, m, method, backend, key_value, signed, seed
):
    dtype = np.int32 if signed else np.uint32
    keys = _keys(n, seed=seed, dtype=dtype)
    vals = jnp.arange(n, dtype=jnp.int32) if key_value else None
    bf = delta_buckets(m, 2**30)
    ref = multisplit(keys, bf, vals, method=method, tile=256, family="onehot",
                     backend=backend)
    out = multisplit(keys, bf, vals, method=method, tile=256, family="packed",
                     backend=backend)
    _assert_equal(out, ref, key_value)
    _assert_equal(out, multisplit_ref(keys, bf, vals), key_value)


def test_packed_family_adversarial_single_bucket_end_to_end():
    """Every key in ONE bucket across full tiles: level-1 lanes saturate in
    every subtile on every tiled backend."""
    n, m = 4096, 256
    keys = jnp.full((n,), 5, jnp.uint32)             # delta bucket 0 for all
    bf = delta_buckets(m, 2**30)
    ref = multisplit_ref(keys, bf, None)
    for backend in ALL_BACKENDS:
        out = multisplit(keys, bf, method="bms", tile=1024, family="packed",
                         backend=backend)
        _assert_equal(out, ref, False)


def test_packed_callable_spec_ids_path():
    """CallableSpec plans feed the packed kernels a precomputed ids strip."""
    n, m = 1500, 64
    keys = _keys(n, seed=3)
    bf = delta_buckets(m, 2**30)
    opaque = from_fn(bf.emit, m, name="opaque")
    ref = multisplit_ref(keys, bf, None)
    for backend in TILED_BACKENDS:
        out = multisplit(keys, opaque, tile=256, family="packed", backend=backend)
        _assert_equal(out, ref, False)


def test_packed_partial_modes_and_layouts():
    m = 64
    bf = delta_buckets(m, 2**30)
    keys = _keys(1000, seed=11)
    ref = multisplit_ref(keys, bf, None)
    for backend in ALL_BACKENDS:
        co = multisplit(keys, bf, mode="counts_only", tile=256, family="packed",
                        backend=backend)
        np.testing.assert_array_equal(
            np.asarray(co.bucket_counts), np.asarray(ref.bucket_counts))
        po = multisplit(keys, bf, mode="positions_only", tile=256, family="packed",
                        backend=backend)
        np.testing.assert_array_equal(
            np.asarray(po.permutation), np.asarray(ref.permutation))
    # batched rows == independent flat calls
    keys2 = _keys(4 * 512, seed=12).reshape(4, 512)
    for backend in ALL_BACKENDS:
        out = batched_multisplit(keys2, bf, tile=256, family="packed", backend=backend)
        for i in range(4):
            ref_i = multisplit_ref(keys2[i], bf, None)
            np.testing.assert_array_equal(np.asarray(out.keys[i]), np.asarray(ref_i.keys))
            np.testing.assert_array_equal(
                np.asarray(out.bucket_counts[i]), np.asarray(ref_i.bucket_counts))
    # ragged segments == independent per-segment flat calls
    keys = _keys(1000, seed=13)
    starts = [0, 100, 400, 400, 900]
    bounds = starts + [1000]
    for backend in ALL_BACKENDS:
        out = segmented_multisplit(keys, bf, starts, tile=256, family="packed",
                                   backend=backend)
        for i in range(len(starts)):
            lo, hi = bounds[i], bounds[i + 1]
            ref_i = multisplit_ref(keys[lo:hi], bf, None)
            np.testing.assert_array_equal(np.asarray(out.keys[lo:hi]), np.asarray(ref_i.keys))
            np.testing.assert_array_equal(
                np.asarray(out.bucket_counts[i]), np.asarray(ref_i.bucket_counts))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_packed_radix_sort_matches_onehot(backend):
    keys = _keys(4096 + 17, seed=7, hi=2**31)
    vals = jnp.arange(keys.shape[0], dtype=jnp.int32)
    k1, v1 = radix_sort(keys, vals, radix_bits=8, backend=backend, family="onehot")
    k2, v2 = radix_sort(keys, vals, radix_bits=8, backend=backend, family="packed")
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(k1), np.sort(np.asarray(keys)))


# ---------------------------------------------------------------------------
# (tile, family) resolution: heuristics, reasons, caches, plan hashing.
# ---------------------------------------------------------------------------

def test_family_heuristic_and_reasons():
    clear_tile_cache()
    for backend in TILED_BACKENDS:
        fam, reason = family_decision(1 << 16, 256, "bms", backend)
        assert fam == "packed" and "m_eff=256" in reason
        fam, reason = family_decision(1 << 16, 4, "bms", backend)
        assert fam == "onehot" and "m_eff=4" in reason
    fam, reason = family_decision(1 << 16, 256, "bms", "reference")
    assert fam == "onehot" and "untiled" in reason
    assert ((1 << 16, 256, "bms", "vmap") in family_decisions())
    # explicit requests are validated but never cached
    clear_tile_cache()
    assert resolve_kernel_family(4096, 8, "bms", "vmap", "packed") == "packed"
    assert (4096, 8, "bms", "vmap") not in _FAMILY_CACHE
    with pytest.raises(ValueError, match="unknown kernel family"):
        resolve_kernel_family(4096, 8, "bms", "vmap", "dense")


def test_family_capability_is_validated_per_backend():
    from repro.core.pipeline.registry import _REGISTRY, Backend, register_backend

    name = "test-onehot-only"
    register_backend(Backend(name=name, description="test", families=("onehot",)))
    try:
        with pytest.raises(ValueError, match="supports kernel families"):
            resolve_kernel_family(4096, 256, "bms", name, "packed")
        assert resolve_kernel_family(4096, 256, "bms", name) == "onehot"
    finally:
        _REGISTRY.pop(name)


def test_heuristic_tile_regression_n1m_m256():
    """Satellite pin: the corrected cost model's tiles for (n=1M, m=256).

    The pre-PR-5 model under-counted the one-hot working set (one T×m̄
    plane, one T×T matrix) and chose tile=1024, whose true fused-postscan
    footprint (two T×m̄ planes + two T×T matrices ≈ 10.5 MB) blows the 8 MB
    budget. The corrected model halves it to 512. Kernel backends are now
    charged what Mosaic allocates for the compiled kernel of either family
    (19 MiB at T=1024 on v5e, over the 16 MiB budget), so the packed tile
    is 512 there too (vmap keeps 4096)."""
    clear_tile_cache()
    assert tiles._heuristic_tile(1 << 20, 256, "bms", "pallas", family="onehot") == 512
    assert tiles._heuristic_tile(1 << 20, 256, "bms", "pallas", family="packed") == 512
    assert tiles._heuristic_tile(1 << 20, 256, "bms", "vmap", family="packed") == 4096
    p = make_plan(1 << 20, 256, method="bms", backend="pallas")
    assert (p.family, p.tile) == ("packed", 512)
    p1h = make_plan(1 << 20, 256, method="bms", backend="pallas", family="onehot")
    assert (p1h.family, p1h.tile) == ("onehot", 512)


def test_explicit_family_does_not_poison_tile_cache():
    """An off-heuristic family override computes its tile under its own cost
    model WITHOUT writing the shape's cache entry (mirrors the explicit-tile
    rule)."""
    clear_tile_cache()
    shape = (1 << 20, 256, "bms", False, "vmap")
    tiles._TILE_CACHE[shape] = 2048                # a measured pin
    p_pk = make_plan(1 << 20, 256, method="bms", backend="vmap")            # auto: packed
    assert p_pk.family == "packed" and tiles._TILE_CACHE[shape] == p_pk.tile == 2048
    p_1h = make_plan(1 << 20, 256, method="bms", backend="vmap", family="onehot")
    assert (p_1h.family, p_1h.tile) == ("onehot", 4096)    # its own model
    assert tiles._TILE_CACHE[shape] == 2048        # auto entry untouched
    assert make_plan(1 << 20, 256, method="bms", backend="vmap").tile == 2048


def test_family_is_a_hashable_plan_axis():
    clear_tile_cache()
    bf = delta_buckets(256, 2**30)
    a = make_plan(4096, 256, bucket_fn=bf)
    b = make_plan(4096, 256, bucket_fn=bf)
    assert a == b and hash(a) == hash(b) and a.family == "packed"
    c = make_plan(4096, 256, bucket_fn=bf, family="onehot")
    assert c != a                                    # family is part of the value


def test_autotune_searches_tile_family_jointly_and_records_reason():
    clear_tile_cache()
    bf = delta_buckets(64, 2**30)
    tuned = tiles.autotune_tile(
        4096, bf, method="bms", backend="vmap", candidates=(512, 1024), trials=1
    )
    assert tuned in (512, 1024)
    assert tiles._TILE_CACHE[(4096, 64, "bms", False, "vmap")] == tuned
    fam, reason = family_decision(4096, 64, "bms", "vmap")
    assert fam in FAMILIES
    assert "autotuned" in reason and str(tuned) in reason
    # the pinned winner is what later plans resolve to
    p = make_plan(4096, 64, method="bms", backend="vmap", bucket_fn=bf)
    assert (p.tile, p.family) == (tuned, fam)


def test_packed_stage_tags():
    clear_tile_cache()
    bf = delta_buckets(256, 2**30)
    vm = make_plan(4096, 256, backend="vmap", bucket_fn=bf)
    assert vm.family == "packed"
    assert vm.stages()[0] == "prescan:vmap-packed"
    assert vm.stages()[-2] == "postscan:fused-reorder-vmap-packed"
    pk = make_plan(4096, 256, backend="pallas-interpret", bucket_fn=bf)
    assert pk.stages()[0] == "prescan:fused-label-kernel-packed"
    # the reference oracle has no tile local solve: no family tag
    rf = make_plan(4096, 256, backend="reference", bucket_fn=bf, family="packed")
    assert rf.stages() == ("direct-solve:reference",)


def test_autotune_family_flip_invalidates_other_kv_tile():
    """Regression: the family decision is shared by both key-value variants
    of a shape, but autotune only measures one — the OTHER variant's cached
    tile (sized under the previous family's cost model) must be dropped,
    not silently served under the flipped family."""
    clear_tile_cache()
    bf = delta_buckets(256, 2**30)
    # key-only plan caches tile 512 under the heuristic 'packed' family
    # (the compiled kernel's T×T planes cap kernel-backend tiles)
    p0 = make_plan(1 << 14, 256, method="bms", backend="pallas-interpret")
    assert (p0.family, p0.tile) == ("packed", 512)
    # force an autotuned family flip via the kv variant (onehot only)
    tiles.autotune_tile(
        1 << 14, bf, method="bms", backend="pallas-interpret", key_value=True,
        candidates=(512,), families=("onehot",), trials=1,
    )
    assert family_decision(1 << 14, 256, "bms", "pallas-interpret")[0] == "onehot"
    # the key-only shape must now re-resolve its tile under 'onehot' — the
    # stale entry sized under the packed model is gone
    assert (1 << 14, 256, "bms", False, "pallas-interpret") not in tiles._TILE_CACHE
    p1 = make_plan(1 << 14, 256, method="bms", backend="pallas-interpret")
    assert (p1.family, p1.tile) == ("onehot", 512)


def test_packed_min_buckets_threshold_is_the_flip_point():
    clear_tile_cache()
    lo = resolve_kernel_family(1 << 16, PACKED_MIN_BUCKETS - 1, "bms", "vmap")
    hi = resolve_kernel_family(1 << 16, PACKED_MIN_BUCKETS, "bms", "vmap")
    assert (lo, hi) == ("onehot", "packed")


def test_packed_min_buckets_matches_measured_crossover():
    """Regression pin for the packed/onehot family crossover.

    The original flip point (64) was a working-set argument. The 8 was
    measured on a CPU host, not on the chip: a key-value flat multisplit at
    n ∈ {2^18, 2^20} had packed winning from m=8 up (1.12–1.25× at m=8,
    ≥1.5× at m=16) and only tying at m=4. It stays pinned until the chip
    benchmark has a cell on each side of it (ROADMAP Queue 1 item 7); move
    it only on that measurement — don't guess."""
    assert PACKED_MIN_BUCKETS == 8
