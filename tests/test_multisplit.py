"""Core multisplit: oracle equivalence + hypothesis property tests."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.identifiers import (
    delta_buckets, even_buckets, from_fn, identity_buckets, range_buckets,
)
from repro.core.multisplit import multisplit, multisplit_ref


def _random_keys(n, seed=0, hi=2**30):
    return jnp.asarray(np.random.RandomState(seed).randint(0, hi, size=n, dtype=np.uint32))


@pytest.mark.parametrize("method", ["dms", "wms", "bms"])
@pytest.mark.parametrize("m", [2, 3, 8, 32, 256])
def test_methods_match_oracle(method, m):
    keys = _random_keys(4096 + 37, seed=m)       # non-tile-multiple on purpose
    vals = jnp.arange(keys.shape[0], dtype=jnp.int32)
    bf = delta_buckets(m, 2**30)
    ref = multisplit_ref(keys, bf, vals)
    out = multisplit(keys, bf, vals, method=method, tile=512)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts))
    np.testing.assert_array_equal(np.asarray(out.permutation), np.asarray(ref.permutation))


@settings(max_examples=25, deadline=None)
@given(
    data=st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=600),
    m=st.integers(2, 64),
    seed=st.integers(0, 3),
)
def test_property_permutation_stable_contiguous(data, m, seed):
    """For ANY input and bucket count: output is a stable bucket-contiguous
    permutation of the input (the definition in paper §3.1)."""
    keys = jnp.asarray(np.array(data, dtype=np.uint32))
    bf = delta_buckets(m, 2**31)
    out = multisplit(keys, bf, jnp.arange(len(data), dtype=jnp.int32), tile=128)
    k_out, v_out = np.asarray(out.keys), np.asarray(out.values)
    ids_out = np.asarray(bf(out.keys))
    # (1) permutation: multiset of keys preserved
    np.testing.assert_array_equal(np.sort(k_out), np.sort(np.asarray(keys)))
    # (2) contiguous, ascending bucket ids
    assert np.all(np.diff(ids_out) >= 0)
    # (3) stability: original indices increase within each bucket
    for b in range(m):
        seg = v_out[ids_out == b]
        assert np.all(np.diff(seg) > 0) if seg.size > 1 else True
    # (4) counts/starts consistent
    counts = np.asarray(out.bucket_counts)
    assert counts.sum() == len(data)
    np.testing.assert_array_equal(
        np.asarray(out.bucket_starts), np.concatenate([[0], np.cumsum(counts)[:-1]])
    )


def test_arbitrary_bucket_function():
    """Keys need not be comparable — e.g. prime/composite style predicates."""
    keys = _random_keys(2000, seed=7, hi=1000)
    bf = from_fn(lambda u: (u % 7 == 0).astype(jnp.int32) + (u % 3 == 0) * 2, 4)
    out = multisplit(keys, bf, tile=256)
    ref = multisplit_ref(keys, bf)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))


def test_identity_and_range_and_even_buckets():
    keys = jnp.asarray(np.random.RandomState(1).randint(0, 16, 512, dtype=np.uint32))
    out = multisplit(keys, identity_buckets(16), tile=64)
    np.testing.assert_array_equal(np.asarray(out.keys), np.sort(np.asarray(keys)))

    fkeys = jnp.asarray(np.random.RandomState(2).uniform(0, 100, 512).astype(np.float32))
    bf = even_buckets(0.0, 100.0, 10)
    out = multisplit(fkeys, bf)
    assert np.all(np.diff(np.asarray(bf(out.keys))) >= 0)

    splitters = jnp.asarray([10.0, 30.0, 70.0])
    bf = range_buckets(splitters)
    out = multisplit(fkeys, bf)
    assert np.all(np.diff(np.asarray(bf(out.keys))) >= 0)


def test_pallas_backed_path_matches():
    keys = _random_keys(4096, seed=3)
    vals = jnp.arange(4096, dtype=jnp.int32)
    bf = delta_buckets(32, 2**30)
    ref = multisplit_ref(keys, bf, vals)
    out = multisplit(keys, bf, vals, method="bms", tile=512,
                     backend="pallas-interpret")
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))


@pytest.mark.parametrize("backend", ["reference", "vmap", "pallas-interpret"])
def test_nan_keys_route_to_last_bucket(backend):
    """ISSUE 7 S1: NaN fails every comparison, so the pre-fix EvenSpec clip
    left NaN keys in an arbitrary bucket. They must all land in the LAST
    bucket (where the +inf pad key lives), on every backend."""
    from repro import ops

    rng = np.random.RandomState(2)
    keys = rng.uniform(0.0, 1.0, 1024).astype(np.float32)
    keys[rng.choice(1024, 50, replace=False)] = np.nan
    out = ops.multisplit(
        jnp.asarray(keys), ops.even_buckets(0.0, 1.0, 8), backend=backend
    )
    counts = np.asarray(out.bucket_counts)
    assert counts.sum() == 1024
    got = np.asarray(out.keys)
    last = int(np.asarray(out.bucket_starts)[-1])
    assert np.isnan(got[:last]).sum() == 0
    assert np.isnan(got[last:last + counts[-1]]).sum() == 50


def test_nan_keys_segmented_route_to_last_bucket_per_segment():
    """S1 on the segmented layout: every segment's NaNs land in that
    segment's OWN last bucket."""
    from repro import ops

    rng = np.random.RandomState(3)
    keys = rng.uniform(0.0, 1.0, 1024).astype(np.float32)
    keys[rng.choice(1024, 60, replace=False)] = np.nan
    starts = np.array([0, 512], np.int32)
    out = ops.segmented_multisplit(
        jnp.asarray(keys), ops.even_buckets(0.0, 1.0, 8), jnp.asarray(starts)
    )
    got = np.asarray(out.keys)
    s_starts = np.asarray(out.bucket_starts)       # (s, m) segment-local
    s_counts = np.asarray(out.bucket_counts)
    for s, (lo, hi) in enumerate(((0, 512), (512, 1024))):
        seg_nans = np.isnan(keys[lo:hi]).sum()
        b0 = lo + s_starts[s, -1]
        span = got[b0:b0 + s_counts[s, -1]]
        assert np.isnan(span).sum() == seg_nans
        assert np.isnan(got[lo:b0]).sum() == 0


def test_binomial_distribution_inputs():
    """Paper §6.4: extreme non-uniform distributions must still be exact."""
    rng = np.random.RandomState(0)
    m = 64
    ids = rng.binomial(m - 1, 0.5, size=5000).astype(np.uint32)
    keys = ids * 1000 + rng.randint(0, 1000, 5000).astype(np.uint32)
    bf = delta_buckets(m, 64000)
    out = multisplit(jnp.asarray(keys), bf, tile=512)
    ref = multisplit_ref(jnp.asarray(keys), bf)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
