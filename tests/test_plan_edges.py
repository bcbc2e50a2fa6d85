"""Plan-layer edge-case matrix + tile-cache regression (ISSUE 2 satellites).

Covers the degenerate shapes every consumer eventually hits: empty inputs,
single elements, inputs smaller than ``_MIN_TILE``, non-tile-multiple n,
single-bucket and 256-bucket problems, all-elements-one-bucket skew, and
empty segments in the segmented path — on every CPU-testable backend.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import plan as msplan
from repro.core.identifiers import delta_buckets, from_fn, identity_buckets
from repro.core.multisplit import (
    batched_multisplit,
    multisplit,
    multisplit_ref,
    segmented_multisplit,
)
from repro.core.sort import radix_sort, segmented_radix_sort

BACKENDS = ["reference", "vmap", "pallas-interpret"]


def _keys(n, seed=0, hi=2**30):
    return jnp.asarray(np.random.RandomState(seed).randint(0, hi, size=n, dtype=np.uint32))


# ---------------------------------------------------------------------------
# Edge-case matrix: n x m x backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", [0, 1, 7, 100, 255, 256, 257, 2048 + 37])
def test_edge_sizes_match_oracle(backend, n):
    """n spans: empty, single, < _MIN_TILE, == tile, tile+1, non-multiple."""
    m = 13
    keys = _keys(n, seed=n + 1)
    vals = jnp.arange(n, dtype=jnp.int32)
    bf = delta_buckets(m, 2**30)
    ref = multisplit_ref(keys, bf, vals)
    out = multisplit(keys, bf, vals, backend=backend)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts))
    np.testing.assert_array_equal(np.asarray(out.bucket_starts), np.asarray(ref.bucket_starts))
    np.testing.assert_array_equal(np.asarray(out.permutation), np.asarray(ref.permutation))
    assert int(out.bucket_counts.sum()) == n


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("m", [1, 2, 256])
def test_edge_bucket_counts(backend, m):
    """m spans: degenerate single bucket, minimal, paper's large-m regime."""
    n = 600 + m
    keys = _keys(n, seed=m)
    bf = delta_buckets(m, 2**30)
    ref = multisplit_ref(keys, bf)
    out = multisplit(keys, bf, tile=256, backend=backend)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts))
    np.testing.assert_array_equal(np.asarray(out.permutation), np.asarray(ref.permutation))


@pytest.mark.parametrize("backend", BACKENDS)
def test_all_elements_one_bucket(backend):
    """Maximal skew: the entire input lands in a single middle bucket."""
    n, m = 777, 16
    keys = jnp.full((n,), 5, jnp.uint32)
    bf = identity_buckets(m)
    out = multisplit(keys, bf, jnp.arange(n, dtype=jnp.int32), tile=128, backend=backend)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(keys))
    np.testing.assert_array_equal(np.asarray(out.values), np.arange(n))  # stable
    counts = np.zeros(m, np.int64)
    counts[5] = n
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), counts)
    np.testing.assert_array_equal(np.asarray(out.permutation), np.arange(n))


def test_n_zero_radix_sort():
    for backend in ("vmap", "pallas-interpret"):
        ks, vs = radix_sort(
            _keys(0), jnp.zeros((0,), jnp.int32), radix_bits=8, backend=backend
        )
        assert ks.shape == (0,) and vs.shape == (0,)


# ---------------------------------------------------------------------------
# Batched / segmented edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_edge_rows(backend):
    """b=1 and n in {0, 1}: batched plans on degenerate shapes."""
    bf = delta_buckets(4, 2**30)
    for b, n in [(1, 0), (1, 1), (3, 0), (3, 1)]:
        keys = _keys(b * n, seed=b * 10 + n).reshape(b, n)
        out = batched_multisplit(keys, bf, backend=backend)
        assert out.keys.shape == (b, n)
        assert out.bucket_counts.shape == (b, 4)
        assert out.permutation.shape == (b, n)
        np.testing.assert_array_equal(
            np.asarray(out.bucket_counts).sum(axis=1), np.full(b, n)
        )


@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_empty_segments(backend):
    """Empty segments anywhere — first, middle, consecutive, last — must
    yield zero count rows and leave neighbours bit-exact."""
    m = 8
    bf = delta_buckets(m, 2**30)
    n = 500
    keys = _keys(n, seed=11)
    vals = jnp.arange(n, dtype=jnp.int32)
    # segment 0 empty (starts[0]==starts[1]==0), two consecutive empties in
    # the middle, and an empty last segment (start == n)
    starts = [0, 0, 200, 200, 200, 500]
    ends = starts[1:] + [n]
    out = segmented_multisplit(keys, bf, starts, vals, tile=128, backend=backend)
    assert out.bucket_counts.shape == (len(starts), m)
    for i, (a, e) in enumerate(zip(starts, ends)):
        if a == e:
            np.testing.assert_array_equal(np.asarray(out.bucket_counts[i]), np.zeros(m))
            np.testing.assert_array_equal(np.asarray(out.bucket_starts[i]), np.zeros(m))
            continue
        ref = multisplit_ref(keys[a:e], bf, vals[a:e])
        np.testing.assert_array_equal(np.asarray(out.keys[a:e]), np.asarray(ref.keys))
        np.testing.assert_array_equal(np.asarray(out.values[a:e]), np.asarray(ref.values))
        np.testing.assert_array_equal(
            np.asarray(out.bucket_counts[i]), np.asarray(ref.bucket_counts)
        )
        np.testing.assert_array_equal(
            np.asarray(out.permutation[a:e]), np.asarray(ref.permutation)
        )


@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_single_segment_equals_flat(backend):
    """s=1 segmented == flat, with (1, m) shaped counts."""
    n, m = 300, 8
    keys = _keys(n, seed=4)
    bf = delta_buckets(m, 2**30)
    flat = multisplit(keys, bf, tile=128, backend=backend)
    seg = segmented_multisplit(keys, bf, [0], tile=128, backend=backend)
    np.testing.assert_array_equal(np.asarray(seg.keys), np.asarray(flat.keys))
    np.testing.assert_array_equal(np.asarray(seg.bucket_counts[0]), np.asarray(flat.bucket_counts))
    np.testing.assert_array_equal(np.asarray(seg.permutation), np.asarray(flat.permutation))


def test_segmented_all_segments_empty():
    """n=0 with several (necessarily empty) segments."""
    bf = delta_buckets(4, 2**30)
    for backend in BACKENDS:
        out = segmented_multisplit(_keys(0), bf, [0, 0, 0], backend=backend)
        assert out.keys.shape == (0,)
        np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.zeros((3, 4)))


def test_segmented_radix_sort_empty_segments():
    keys = _keys(300, seed=9, hi=2**16)
    starts = [0, 0, 150, 300]
    ks, _ = segmented_radix_sort(keys, starts, radix_bits=4, key_bits=16, tile=128)
    np.testing.assert_array_equal(np.asarray(ks[0:150]), np.sort(np.asarray(keys[0:150])))
    np.testing.assert_array_equal(np.asarray(ks[150:300]), np.sort(np.asarray(keys[150:300])))


# ---------------------------------------------------------------------------
# Plan validation of the new layouts
# ---------------------------------------------------------------------------

def test_layout_validation():
    with pytest.raises(ValueError):
        msplan.make_plan(100, 4, batch=2, segments=2)        # mutually exclusive
    with pytest.raises(ValueError):
        msplan.make_plan(100, 4, batch=0)
    with pytest.raises(ValueError):
        msplan.make_plan(100, 4, segments=0)
    bf = delta_buckets(4)
    p = msplan.make_plan(100, 4, bucket_fn=bf)
    with pytest.raises(ValueError):                          # not segmented
        p(_keys(100), segment_starts=jnp.zeros((1,), jnp.int32))
    ps = msplan.make_plan(100, 4, bucket_fn=bf, segments=2)
    with pytest.raises(ValueError):                          # starts required
        ps(_keys(100))
    with pytest.raises(ValueError):                          # wrong starts shape
        ps(_keys(100), segment_starts=jnp.zeros((3,), jnp.int32))
    pb = msplan.make_plan(50, 4, bucket_fn=bf, batch=2)
    with pytest.raises(ValueError):                          # wrong batch shape
        pb(_keys(100).reshape(4, 25))


def test_stages_mark_layouts():
    bf = delta_buckets(8)
    fl = msplan.make_plan(256, 8, bucket_fn=bf)
    bt = msplan.make_plan(256, 8, bucket_fn=bf, batch=4)
    sg = msplan.make_plan(256, 8, bucket_fn=bf, segments=4)
    assert not fl.stages()[0].startswith("layout:")
    assert bt.stages()[0] == "layout:batched[4]"
    assert sg.stages()[0] == "layout:segmented[4]"
    assert bt.stages()[1:] == fl.stages()
    assert sg.stages()[1:] == fl.stages()


# ---------------------------------------------------------------------------
# _TILE_CACHE regression: explicit tile= must not poison the autotune cache
# ---------------------------------------------------------------------------

def test_explicit_tile_does_not_poison_cache():
    """Regression: a one-off ``tile=`` override must leave subsequent
    same-shape plans resolving to the heuristic/autotuned tile."""
    msplan.clear_tile_cache()
    shape = (1 << 16, 32, "bms", False, "vmap")
    heuristic = msplan._heuristic_tile(1 << 16, 32, "bms", "vmap")
    assert heuristic != 64  # the override below must be distinguishable

    p_override = msplan.make_plan(1 << 16, 32, method="bms", backend="vmap", tile=64)
    assert p_override.tile == 64
    # the override was honored but NOT cached
    assert shape not in msplan._TILE_CACHE or msplan._TILE_CACHE[shape] != 64

    p_after = msplan.make_plan(1 << 16, 32, method="bms", backend="vmap")
    assert p_after.tile == heuristic
    assert msplan._TILE_CACHE[shape] == heuristic

    # and an override AFTER the cache is warm neither reads nor clobbers it
    p_again = msplan.make_plan(1 << 16, 32, method="bms", backend="vmap", tile=128)
    assert p_again.tile == 128
    assert msplan._TILE_CACHE[shape] == heuristic


def test_autotuned_tile_survives_override():
    """An autotune-pinned winner stays pinned across explicit overrides."""
    msplan.clear_tile_cache()
    bf = delta_buckets(8, 2**30)
    tuned = msplan.autotune_tile(
        4096, bf, method="bms", backend="vmap", candidates=(256, 1024), trials=1
    )
    msplan.make_plan(4096, 8, method="bms", backend="vmap", bucket_fn=bf, tile=32)
    assert msplan._TILE_CACHE[(4096, 8, "bms", False, "vmap")] == tuned
    assert msplan.make_plan(4096, 8, method="bms", backend="vmap", bucket_fn=bf).tile == tuned


def test_segmented_tile_cache_keyed_on_combined_width():
    """Segmented plans budget VMEM for the COMBINED (s*m) scan width, so
    their cache entries must not collide with the flat (n, m) shape."""
    msplan.clear_tile_cache()
    bf = delta_buckets(4)
    flat = msplan.make_plan(1 << 18, 4, backend="pallas-interpret", bucket_fn=bf)
    seg = msplan.make_plan(
        1 << 18, 4, backend="pallas-interpret", bucket_fn=bf, segments=64
    )
    assert (1 << 18, 4, "bms", False, "pallas-interpret") in msplan._TILE_CACHE
    assert (1 << 18, 256, "bms", False, "pallas-interpret") in msplan._TILE_CACHE
    # the combined width flips the 256-wide shape into the PACKED family
    # (PR-5); on kernel backends both families are charged the compiled
    # kernel's VMEM, whose T×T planes dominate at these widths, so the
    # 256-wide shape keeps the narrow flat shape's tile
    assert seg.family == "packed" and flat.family == "onehot"
    assert seg.tile == flat.tile
    # within the one-hot family the old rule still holds at a width that
    # pushes the working set past the budget floor
    seg1h = msplan.make_plan(
        1 << 18, 4, backend="pallas-interpret", bucket_fn=bf, segments=1024,
        family="onehot",
    )
    assert seg1h.tile < flat.tile
