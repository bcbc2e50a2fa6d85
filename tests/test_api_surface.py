"""Public-API snapshot (ISSUE 4 satellite): `repro.ops` is the stable
surface downstream PRs (sharded/multi-host, new backends) program against.
This test pins ``__all__`` and the operator signatures — changing either is
a deliberate, reviewed act, not a side effect."""

import inspect

import pytest

from repro import ops

EXPECTED_ALL = (
    "BucketSpec", "BitfieldSpec", "CallableSpec", "DeltaSpec", "EvenSpec",
    "IdentitySpec", "RangeSpec",
    "as_spec", "delta_buckets", "even_buckets", "from_fn",
    "identity_buckets", "radix_buckets", "range_buckets",
    "MultisplitResult",
    "multisplit", "multisplit_key_value", "segmented_multisplit",
    "histogram", "radix_sort", "segmented_radix_sort",
    "set_autotune",
    "set_strict", "set_verify",
)

EXPECTED_SIGNATURES = {
    # PR-5 additively appended keyword-only ``family`` (kernel family,
    # DESIGN.md §12) to every plan-backed op, per the §11 stability policy.
    # ISSUE 6 additively appended keyword-only ``fuse_digits`` (fused
    # two-digit radix pairs, DESIGN.md §13) to the two radix sorts.
    # ``backend=None`` takes the platform default (``default_backend``:
    # compiled pallas on a TPU for 32-bit keys, else vmap).
    "multisplit": (
        "(keys, spec, values=None, *, method='bms', backend=None, "
        "tile=None, mode='reorder', family=None)"
    ),
    "multisplit_key_value": (
        "(keys, values, spec, *, method='bms', backend=None, tile=None, "
        "family=None)"
    ),
    "segmented_multisplit": (
        "(keys, spec, segment_starts, values=None, *, method='bms', "
        "backend=None, tile=None, mode='reorder', family=None)"
    ),
    "histogram": "(keys, spec, *, backend=None, tile=None, family=None)",
    "radix_sort": (
        "(keys, values=None, *, radix_bits=8, key_bits=32, method='bms', "
        "backend=None, tile=None, family=None, fuse_digits=False)"
    ),
    "segmented_radix_sort": (
        "(keys, segment_starts, values=None, *, radix_bits=8, key_bits=32, "
        "method='bms', backend=None, tile=None, family=None, "
        "fuse_digits=False)"
    ),
    "delta_buckets": "(num_buckets, key_max=1073741824)",
    "identity_buckets": "(num_buckets)",
    "radix_buckets": "(pass_idx, radix_bits)",
    "range_buckets": "(splitters)",
    "even_buckets": "(lo, hi, num_buckets)",
    "from_fn": "(fn, num_buckets, name='user')",
    # ISSUE 7 additively appended the self-tuning opt-in (DESIGN.md §14).
    "set_autotune": (
        "(enabled=None, *, cache_dir=None, persist=None, trials=None, "
        "candidates=None)"
    ),
    # ISSUE 10 additively appended the resilience opt-ins (DESIGN.md §17).
    "set_strict": "(enabled)",
    "set_verify": "(level)",
}


def _normalize(sig: inspect.Signature) -> str:
    # strip annotations; keep names, kinds and defaults
    params = [p.replace(annotation=inspect.Parameter.empty)
              for p in sig.parameters.values()]
    return str(inspect.Signature(params))


def test_all_is_pinned():
    assert tuple(ops.__all__) == EXPECTED_ALL
    for name in ops.__all__:
        assert hasattr(ops, name), f"__all__ names missing symbol {name}"


@pytest.mark.parametrize("name", sorted(EXPECTED_SIGNATURES))
def test_operator_signatures_are_pinned(name):
    got = _normalize(inspect.signature(getattr(ops, name)))
    assert got == EXPECTED_SIGNATURES[name], (
        f"ops.{name} signature changed:\n  pinned: {EXPECTED_SIGNATURES[name]}"
        f"\n  actual: {got}\nUpdate the public-API stability policy "
        "(DESIGN.md §11) and this snapshot together."
    )


def test_result_contract():
    fields = ops.MultisplitResult._fields
    assert fields == ("keys", "values", "bucket_starts", "bucket_counts", "permutation")


def test_specs_in_all_are_hashable_types():
    import dataclasses

    for name in ("DeltaSpec", "BitfieldSpec", "RangeSpec", "EvenSpec",
                 "IdentitySpec", "CallableSpec"):
        cls = getattr(ops, name)
        assert issubclass(cls, ops.BucketSpec)
    s = ops.DeltaSpec(8, 1 << 20)
    assert dataclasses.is_dataclass(s) and hash(s) == hash(ops.DeltaSpec(8, 1 << 20))
