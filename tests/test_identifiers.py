"""BucketSpec layer (ISSUE 4): value hashing / equality, pytree staticness,
the range_buckets validation + dtype-max fixes, pad-key invariants, and
as_spec."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.identifiers import (
    BitfieldSpec,
    DeltaSpec,
    EvenSpec,
    IdentitySpec,
    RangeSpec,
    as_spec,
    delta_buckets,
    even_buckets,
    from_fn,
    identity_buckets,
    radix_buckets,
    range_buckets,
)

ALL_SPECS = [
    delta_buckets(32, 2**30),
    identity_buckets(16),
    radix_buckets(1, 8),
    range_buckets([100, 10_000, 2**29]),
    even_buckets(0.0, 1024.0, 64),
]


# ---------------------------------------------------------------------------
# Value hashing / equality (the jit-retrace satellite)
# ---------------------------------------------------------------------------

def test_equal_constructions_are_equal_and_hash_equal():
    pairs = [
        (delta_buckets(32, 2**30), DeltaSpec(32, 2**30)),
        (identity_buckets(16), IdentitySpec(16)),
        (radix_buckets(2, 7), BitfieldSpec(14, 7)),
        (range_buckets([3, 1, 2]), RangeSpec((1, 2, 3))),
        (even_buckets(0, 10, 5), EvenSpec(0.0, 10.0, 5)),
    ]
    for a, b in pairs:
        assert a == b and hash(a) == hash(b), (a, b)
    assert delta_buckets(32) != delta_buckets(16)
    assert BitfieldSpec(0, 8) != BitfieldSpec(8, 8)


def test_specs_are_frozen_and_pytree_static():
    for spec in ALL_SPECS:
        with pytest.raises(dataclasses.FrozenInstanceError):
            spec.num_buckets = 3  # type: ignore[misc]
        leaves, treedef = jax.tree_util.tree_flatten(spec)
        assert leaves == []                      # no traced children
        assert jax.tree_util.tree_unflatten(treedef, []) == spec


def test_equal_specs_share_one_jit_trace():
    """THE retrace regression: two equal spec instances must not retrace,
    whether the spec rides as a pytree argument or a static argument."""
    keys = jnp.asarray(np.random.RandomState(0).randint(0, 2**30, 512, dtype=np.uint32))

    traces = []

    @jax.jit
    def as_pytree(keys, spec):
        traces.append(1)
        return spec.emit(keys).sum()

    as_pytree(keys, delta_buckets(32))
    as_pytree(keys, DeltaSpec(32, 2**30))
    assert len(traces) == 1

    traces2 = []

    def g(keys, spec):
        traces2.append(1)
        return spec.emit(keys).sum()

    jg = jax.jit(g, static_argnums=1)
    jg(keys, range_buckets([10, 20]))
    jg(keys, range_buckets([20, 10]))             # sorted-equal
    assert len(traces2) == 1

    # distinct specs DO retrace (sanity that the counter works)
    jg(keys, range_buckets([10, 30]))
    assert len(traces2) == 2


# ---------------------------------------------------------------------------
# range_buckets: validation, sorting, dtype-max keys (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_range_buckets_sorts_splitters():
    assert range_buckets([70, 10, 30]).splitters == (10, 30, 70)
    u = jnp.asarray([0, 10, 29, 30, 69, 70, 95], jnp.uint32)
    got = range_buckets([70, 10, 30])(u)
    want = range_buckets([10, 30, 70])(u)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), [0, 1, 1, 2, 2, 3, 3])


def test_range_buckets_validates():
    with pytest.raises(ValueError):
        range_buckets(np.zeros((2, 2)))
    with pytest.raises(ValueError):
        range_buckets([1.0, float("nan")])


def test_range_buckets_dtype_max_keys_no_overflow():
    """uint32 keys above the last splitter — all the way to the dtype max —
    must land in the LAST bucket (the pre-PR-4 searchsorted promoted mixed
    dtypes and wrapped large uint32 keys negative)."""
    spec = range_buckets([100, 1000])
    u = jnp.asarray([99, 100, 1000, 2**31, 2**32 - 1], jnp.uint32)
    np.testing.assert_array_equal(np.asarray(spec(u)), [0, 1, 2, 2, 2])
    # signed keys with the same spec
    i = jnp.asarray([-5, 99, 2**31 - 1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(spec(i)), [0, 0, 2])


def test_range_buckets_matches_searchsorted_on_floats():
    rng = np.random.RandomState(3)
    keys = jnp.asarray(rng.uniform(0, 1000, 5000).astype(np.float32))
    sp = np.sort(rng.uniform(0, 1000, 15)).astype(np.float32)
    got = range_buckets(sp)(keys)
    want = jnp.searchsorted(jnp.asarray(sp), keys, side="right").astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_range_emit_in_kernel_matches_emit():
    """The unrolled in-kernel form and the host-side binary search are the
    same function (incl. duplicate splitters and dtype-extreme keys)."""
    spec = range_buckets([10, 10, 30, 70, 70])
    for keys in (
        jnp.asarray([0, 9, 10, 11, 30, 69, 70, 71, 2**32 - 1], jnp.uint32),
        jnp.asarray(np.random.RandomState(0).randint(0, 100, 500), jnp.int32),
        jnp.asarray(np.random.RandomState(1).uniform(0, 100, 500), jnp.float32),
    ):
        np.testing.assert_array_equal(
            np.asarray(spec.emit(keys)), np.asarray(spec.emit_in_kernel(keys))
        )


def test_range_splitters_above_int32_max_on_uint32_keys():
    """Splitters in the upper half of the uint32 domain must not weak-type
    into an int32 overflow on either emit form (regression)."""
    spec = range_buckets([2**31 + 5])
    u = jnp.asarray([5, 2**31 + 4, 2**31 + 5, 2**32 - 1], jnp.uint32)
    np.testing.assert_array_equal(np.asarray(spec.emit(u)), [0, 0, 1, 1])
    np.testing.assert_array_equal(np.asarray(spec.emit_in_kernel(u)), [0, 0, 1, 1])


def test_range_splitters_out_of_key_dtype_range_rejected():
    """A splitter no key can reach would make the last bucket unreachable
    (and break the pad invariant): rejected at emit, not silently clamped."""
    spec = range_buckets([2**33])
    with pytest.raises(ValueError, match="out of range"):
        spec.emit(jnp.asarray([0, 2**32 - 1], jnp.uint32))
    with pytest.raises(ValueError, match="out of range"):
        range_buckets([-1]).emit(jnp.asarray([0], jnp.uint32))
    # float keys: representable, no rejection
    assert int(spec.emit(jnp.asarray([1.0], jnp.float32))[0]) == 0


def test_range_buckets_fractional_splitters_int_keys():
    """Fractional splitters with integer keys compare in float (old
    promotion semantics), not by truncated-integer splitters."""
    spec = range_buckets([10.5])
    np.testing.assert_array_equal(
        np.asarray(spec(jnp.asarray([10, 11], jnp.int32))), [0, 1]
    )


# ---------------------------------------------------------------------------
# pad keys: every spec's pad lands in the LAST bucket
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.int32, jnp.float32])
def test_pad_key_lands_in_last_bucket(spec, dtype):
    if spec.name.startswith("even") and dtype != jnp.float32:
        pytest.skip("even buckets are float specs")
    if spec.name.startswith(("delta", "radix")) and dtype == jnp.float32:
        pytest.skip("integer-domain specs")
    pad = jnp.full((4,), spec.pad_key(dtype), dtype)
    np.testing.assert_array_equal(
        np.asarray(spec.emit(pad)), np.full(4, spec.num_buckets - 1)
    )


def test_bitfield_pad_key_is_all_ones_every_pass():
    """The chained-radix invariant: ONE pad key whose digit is m-1 in EVERY
    pass of the schedule."""
    for dtype in (jnp.uint32, jnp.int32):
        pad = jnp.full((1,), BitfieldSpec(0, 8).pad_key(dtype), dtype)
        for shift in range(0, 32, 8):
            assert int(BitfieldSpec(shift, 8).emit(pad)[0]) == 255


# ---------------------------------------------------------------------------
# ISSUE 7 satellites: NaN routing (S1) and float-key rejection (S4)
# ---------------------------------------------------------------------------

def test_even_spec_nan_routes_to_last_bucket():
    """NaN fails every comparison, so the old clip left it wherever the
    scaled id landed (ISSUE 7 S1). It must route DETERMINISTICALLY to the
    last bucket — the same one the +inf pad key lands in."""
    s = EvenSpec(0.0, 1.0, 8)
    keys = jnp.asarray(
        [0.1, float("nan"), 2.0, -1.0, float("inf"), float("-inf")],
        jnp.float32,
    )
    np.testing.assert_array_equal(np.asarray(s.emit(keys)), [0, 7, 7, 0, 7, 0])
    pad = jnp.full((2,), s.pad_key(jnp.float32), jnp.float32)
    np.testing.assert_array_equal(np.asarray(s.emit(pad)), [7, 7])


def test_bitfield_spec_rejects_float_keys():
    """BitfieldSpec.pad_key on a float dtype used to return -1 (the int cast
    of the float max) and emit produced garbage digits (ISSUE 7 S4): both
    must refuse float keys loudly."""
    s = BitfieldSpec(0, 8)
    with pytest.raises(TypeError, match="integer keys"):
        s.pad_key(jnp.float32)
    with pytest.raises(TypeError, match="integer keys"):
        s.emit(jnp.ones((4,), jnp.float32))
    # integer dtypes keep working
    assert int(s.pad_key(jnp.uint32)) == 0xFFFFFFFF


# ---------------------------------------------------------------------------
# as_spec
# ---------------------------------------------------------------------------

def test_as_spec():
    s = delta_buckets(4)
    assert as_spec(s) is s
    assert as_spec(from_fn(lambda u: u, 4)).num_buckets == 4
    with pytest.raises(TypeError):
        as_spec(lambda u: u)                     # bare callable: no num_buckets
    with pytest.raises(TypeError):
        as_spec(7)


def test_callable_spec_pad_key_raises():
    """An arbitrary fn cannot honor the pad-lands-in-bucket-m-1 contract:
    pad_key must refuse loudly, not silently pad with the dtype max (the
    layout pads CallableSpec plans on the label side only)."""
    with pytest.raises(NotImplementedError):
        from_fn(lambda u: u % 3, 3).pad_key(jnp.uint32)


def test_callable_specs_hash_by_function_identity():
    fn = lambda u: (u & 1).astype(jnp.int32)     # noqa: E731
    assert from_fn(fn, 2) == from_fn(fn, 2)
    assert from_fn(fn, 2) != from_fn(lambda u: (u & 1).astype(jnp.int32), 2)
