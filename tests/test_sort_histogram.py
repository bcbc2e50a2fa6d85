"""Multisplit-sort (paper §7.1) and device histogram (paper §7.3)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.histogram import histogram_even, histogram_range
from repro.core.identifiers import delta_buckets
from repro.core.multisplit import multisplit_ref
from repro.core.sort import direct_sort_multisplit, radix_sort, rb_sort_multisplit


@pytest.mark.parametrize("radix_bits", [4, 6, 7, 8])
def test_radix_sort_vs_numpy(radix_bits):
    rng = np.random.RandomState(radix_bits)
    keys = rng.randint(0, 2**32, size=5000, dtype=np.uint32)
    vals = np.arange(5000, dtype=np.int32)
    ks, vs = radix_sort(jnp.asarray(keys), jnp.asarray(vals), radix_bits=radix_bits)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), keys[order])
    np.testing.assert_array_equal(np.asarray(vs), vals[order])


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=400))
def test_property_radix_sort(data):
    keys = np.array(data, dtype=np.uint32)
    ks, _ = radix_sort(jnp.asarray(keys), radix_bits=8)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(keys))


@pytest.mark.parametrize("radix_bits", [4, 8])
@pytest.mark.parametrize("method", ["dms", "bms"])
def test_radix_sort_fused_pallas(radix_bits, method):
    """The fused in-kernel digit path (no host labels) vs numpy stable sort."""
    rng = np.random.RandomState(radix_bits + 100)
    keys = rng.randint(0, 2**32, size=3000, dtype=np.uint32)
    vals = np.arange(3000, dtype=np.int32)
    ks, vs = radix_sort(
        jnp.asarray(keys), jnp.asarray(vals),
        radix_bits=radix_bits, method=method, backend="pallas-interpret", tile=512,
    )
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), keys[order])
    np.testing.assert_array_equal(np.asarray(vs), vals[order])


def test_radix_sort_rejects_float_keys():
    """ISSUE 7 S4: BitfieldSpec digit extraction on float keys silently
    produced garbage (its pad_key cast to -1). Radix plans must refuse
    non-integer key dtypes with an actionable error instead."""
    from repro.core.sort import segmented_radix_sort

    f = jnp.ones((64,), jnp.float32)
    with pytest.raises(TypeError, match="integer keys"):
        radix_sort(f)
    with pytest.raises(TypeError, match="integer keys"):
        segmented_radix_sort(f, jnp.asarray([0, 32], jnp.int32))
    with pytest.raises(TypeError, match="integer keys"):
        radix_sort(f, fuse_digits=True)


def test_rb_sort_baseline_matches_multisplit():
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 2**30, 4096, dtype=np.uint32))
    vals = jnp.arange(4096, dtype=jnp.int32)
    bf = delta_buckets(32, 2**30)
    ref = multisplit_ref(keys, bf, vals)
    rb = rb_sort_multisplit(keys, bf, vals)
    np.testing.assert_array_equal(np.asarray(rb.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(rb.values), np.asarray(ref.values))
    np.testing.assert_array_equal(np.asarray(rb.bucket_counts), np.asarray(ref.bucket_counts))


def test_direct_sort_baseline():
    keys = jnp.asarray(np.random.RandomState(0).randint(0, 2**30, 1000, dtype=np.uint32))
    ks, _ = direct_sort_multisplit(keys)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(np.asarray(keys)))


@pytest.mark.parametrize("m", [2, 16, 64, 256])
@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_histogram_even(m, backend):
    keys = jnp.asarray(np.random.RandomState(m).uniform(0, 1024, 20000).astype(np.float32))
    h = histogram_even(keys, 0.0, 1024.0, m, backend=backend)
    expect, _ = np.histogram(np.asarray(keys), bins=m, range=(0, 1024))
    np.testing.assert_array_equal(np.asarray(h), expect)


def test_histogram_range():
    rng = np.random.RandomState(1)
    keys = jnp.asarray(rng.uniform(0, 1000, 10000).astype(np.float32))
    splitters = jnp.asarray(np.sort(rng.uniform(0, 1000, 15)).astype(np.float32))
    h = histogram_range(keys, splitters)
    expect, _ = np.histogram(
        np.asarray(keys), bins=np.concatenate([[-np.inf], np.asarray(splitters), [np.inf]])
    )
    np.testing.assert_array_equal(np.asarray(h), expect)


# ---------------------------------------------------------------------------
# Histogram edges (ISSUE 3 satellite: the counts_only migration must handle
# the degenerate shapes the old private-_pad_to_tiles path special-cased)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_histogram_empty_input(backend):
    h = histogram_even(jnp.zeros((0,), jnp.float32), 0.0, 1.0, 8, backend=backend)
    np.testing.assert_array_equal(np.asarray(h), np.zeros(8))


@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_histogram_single_bucket(backend):
    keys = jnp.asarray(np.random.RandomState(0).uniform(0, 9, 777).astype(np.float32))
    h = histogram_even(keys, 0.0, 9.0, 1, backend=backend)
    np.testing.assert_array_equal(np.asarray(h), np.asarray([777]))


@pytest.mark.parametrize("n", [1, 255, 257, 4096 + 37])
def test_histogram_non_multiple_of_tile(n):
    keys = jnp.asarray(np.random.RandomState(n).uniform(0, 32, n).astype(np.float32))
    for backend in ("vmap", "pallas-interpret"):
        h = histogram_even(keys, 0.0, 32.0, 8, tile=256, backend=backend)
        expect, _ = np.histogram(np.asarray(keys), bins=8, range=(0, 32))
        np.testing.assert_array_equal(np.asarray(h), expect)
        assert int(h.sum()) == n


def test_histogram_resolves_tile_through_shared_cache():
    """The old code hardcoded HIST_TILE=4096 and reached into
    ms._pad_to_tiles; now tile=None goes through resolve_tile and lands in
    the shared per-shape cache."""
    from repro.core.pipeline import tiles

    tiles.clear_tile_cache()
    keys = jnp.asarray(np.random.RandomState(2).uniform(0, 8, 20000).astype(np.float32))
    histogram_even(keys, 0.0, 8.0, 8)
    assert (20000, 8, "bms", False, "vmap") in tiles._TILE_CACHE
