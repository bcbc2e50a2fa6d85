"""The plan/dispatch layer (repro.core.plan): backend x method x key-value
equivalence against the reference oracle, fused-pipeline acceptance checks,
tile resolution cache, and the fused radix path."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import plan as msplan
from repro.core.identifiers import delta_buckets
from repro.core.multisplit import multisplit, multisplit_ref, multisplit_unfused
from repro.core.sort import radix_sort

BACKENDS = ["reference", "vmap", "pallas-interpret"]


def _keys(n, seed=0, hi=2**30):
    return jnp.asarray(np.random.RandomState(seed).randint(0, hi, size=n, dtype=np.uint32))


# ---------------------------------------------------------------------------
# Plan resolution
# ---------------------------------------------------------------------------

def test_make_plan_resolves_tile_and_caches():
    msplan.clear_tile_cache()
    p1 = msplan.make_plan(1 << 16, 32, method="bms", backend="vmap")
    p2 = msplan.make_plan(1 << 16, 32, method="bms", backend="vmap")
    assert p1.tile == p2.tile
    assert (1 << 16, 32, "bms", False, "vmap") in msplan._TILE_CACHE
    # explicit tile overrides the cache
    p3 = msplan.make_plan(1 << 16, 32, method="bms", backend="vmap", tile=512)
    assert p3.tile == 512


def test_tile_heuristic_respects_vmem_budget_on_pallas():
    # large m on a pallas backend must shrink the ONE-HOT-family tile below
    # the BMS default: kernel backends are charged the scoped VMEM Mosaic
    # allocates for the compiled fused postscan (tiles._kernel_cost_bytes),
    # and the chosen tile is the largest power of two within the budget
    from repro.core.pipeline import tiles

    msplan.clear_tile_cache()
    p = msplan.make_plan(1 << 20, 256, method="bms", backend="pallas", family="onehot")
    t = p.tile
    cost = lambda t: tiles._family_cost_bytes(t, 256, "onehot", oblivious=True)
    assert cost(t) <= msplan._VMEM_BUDGET_BYTES < cost(2 * t)
    assert msplan._MIN_TILE <= t < msplan.BMS_TILE
    # the compiled kernels of both families carry the same T×T planes, so
    # the packed family gets the same tile on kernel backends
    pk = msplan.make_plan(1 << 20, 256, method="bms", backend="pallas", family="packed")
    assert pk.tile == p.tile


def test_small_input_gets_small_tile():
    p = msplan.make_plan(300, 8, method="bms", backend="vmap")
    assert p.tile <= 512


def test_plan_validates_inputs():
    with pytest.raises(ValueError):
        msplan.make_plan(100, 4, method="zms")
    with pytest.raises(ValueError):
        msplan.make_plan(100, 4, backend="cuda")
    p = msplan.make_plan(100, 4, key_value=True, bucket_fn=delta_buckets(4))
    with pytest.raises(ValueError):
        p(_keys(100))                      # resolved key-value, called key-only
    with pytest.raises(ValueError):
        p(_keys(64), jnp.arange(64))       # wrong n


def test_stages_description():
    from repro.core.identifiers import from_fn

    # m=4 sits below PACKED_MIN_BUCKETS, so the stage names carry no
    # family tag (the packed variants are asserted in test_packed.py)
    bf = delta_buckets(4)
    vm = msplan.make_plan(1024, 4, method="bms", backend="vmap", bucket_fn=bf)
    assert vm.stages()[-2] == "postscan:fused-reorder-vmap"
    # fusable specs label-fuse on kernel backends (PR-4): ids in-register
    pk = msplan.make_plan(1024, 4, method="wms", backend="pallas-interpret", bucket_fn=bf)
    assert pk.stages()[0] == "prescan:fused-label-kernel"
    assert pk.stages()[-2] == "postscan:fused-label-reorder-kernel"
    # the callable escape hatch keeps the materialized-labels stages
    cb = msplan.make_plan(
        1024, 4, method="wms", backend="pallas-interpret",
        bucket_fn=from_fn(lambda u: u.astype("int32") % 4, 4),
    )
    assert cb.stages()[0] == "prescan:kernel"
    assert cb.stages()[-2] == "postscan:fused-reorder-kernel"
    rx = msplan.make_radix_plan(
        1024, 0, 8, method="bms", backend="pallas-interpret", family="onehot"
    )
    assert rx.stages()[0] == "prescan:radix-fused-kernel"
    assert rx.stages()[-2] == "postscan:radix-fused-reorder-kernel"
    # the 256-bucket digit auto-resolves to the packed family (PR-5), which
    # tags the local-solve stages
    rx_auto = msplan.make_radix_plan(1024, 0, 8, method="bms", backend="pallas-interpret")
    assert rx_auto.family == "packed"
    assert rx_auto.stages()[0] == "prescan:radix-fused-kernel-packed"
    assert rx_auto.stages()[-2] == "postscan:radix-fused-reorder-kernel-packed"


# ---------------------------------------------------------------------------
# Equivalence sweep: backends x methods x key-only/key-value x ragged n
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", ["dms", "wms", "bms"])
@pytest.mark.parametrize("key_value", [False, True])
@pytest.mark.parametrize("n", [2048, 2048 + 37])        # tile-divisible and not
def test_plan_backends_match_reference(backend, method, key_value, n):
    m = 13
    keys = _keys(n, seed=(sum(map(ord, method)) * 1009 + n) % 100003)  # deterministic per case
    vals = jnp.arange(n, dtype=jnp.int32) if key_value else None
    bf = delta_buckets(m, 2**30)
    ref = multisplit_ref(keys, bf, vals)
    out = multisplit(keys, bf, vals, method=method, tile=256, backend=backend)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts))
    np.testing.assert_array_equal(np.asarray(out.bucket_starts), np.asarray(ref.bucket_starts))
    np.testing.assert_array_equal(np.asarray(out.permutation), np.asarray(ref.permutation))
    if key_value:
        np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))


@pytest.mark.parametrize("method", ["dms", "wms", "bms"])
def test_fused_matches_legacy_unfused(method):
    n, m = 4096 + 17, 32
    keys = _keys(n, seed=5)
    vals = jnp.arange(n, dtype=jnp.int32)
    bf = delta_buckets(m, 2**30)
    legacy = multisplit_unfused(keys, bf, vals, method=method, tile=512)
    fused = multisplit(keys, bf, vals, method=method, tile=512)
    for a, b in zip(fused[:4], legacy[:4]):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(fused.permutation), np.asarray(legacy.permutation))


# ---------------------------------------------------------------------------
# Acceptance: the fused kernel is the ONLY postscan/reorder entry point
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["wms", "bms"])
def test_pallas_postscan_goes_only_through_fused_kernel(method, monkeypatch):
    from repro.kernels import ops as kops

    def boom(*a, **k):
        raise AssertionError("unfused postscan/reorder kernel was called")

    monkeypatch.setattr(kops, "tile_positions", boom)
    monkeypatch.setattr(kops, "tile_reorder", boom)
    keys = _keys(2048 + 9, seed=2)
    vals = jnp.arange(keys.shape[0], dtype=jnp.int32)
    bf = delta_buckets(16, 2**30)
    out = multisplit(keys, bf, vals, method=method, tile=256, use_pallas=True)
    ref = multisplit_ref(keys, bf, vals)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))


def test_radix_sort_pallas_never_materializes_labels(monkeypatch):
    """radix_sort(use_pallas=True): digit extraction happens inside the fused
    kernels — no BucketIdentifier is ever evaluated host-side."""
    from repro.core import identifiers

    calls = []
    orig = identifiers.BucketIdentifier.__call__

    def spy(self, keys):
        calls.append(self.name)
        return orig(self, keys)

    monkeypatch.setattr(identifiers.BucketIdentifier, "__call__", spy)
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 2**32, 3000, dtype=np.uint32))
    vals = jnp.arange(3000, dtype=jnp.int32)
    ks, vs = radix_sort(keys, vals, radix_bits=8, use_pallas=True, tile=512)
    assert calls == [], f"host-side label materialization via {calls}"
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(keys)[order])
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(vals)[order])


# ---------------------------------------------------------------------------
# Fused radix path vs the platform sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
@pytest.mark.parametrize("method", ["dms", "bms"])
def test_radix_plan_backends_vs_jnp_sort(backend, method):
    rng = np.random.RandomState(7)
    keys = jnp.asarray(rng.randint(0, 2**32, 2500, dtype=np.uint32))
    ks, _ = radix_sort(keys, radix_bits=8, method=method, backend=backend, tile=512)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(np.asarray(keys)))


def test_radix_key_value_pallas_vs_jnp_sort():
    rng = np.random.RandomState(11)
    keys = jnp.asarray(rng.randint(0, 2**32, 1500, dtype=np.uint32))
    vals = jnp.asarray(rng.randint(0, 2**31, 1500, dtype=np.int32))
    ks, vs = radix_sort(keys, vals, radix_bits=4, backend="pallas-interpret", tile=256)
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(keys)[order])
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(vals)[order])


# ---------------------------------------------------------------------------
# Autotune cache
# ---------------------------------------------------------------------------

def test_autotune_pins_tile_in_cache():
    msplan.clear_tile_cache()
    bf = delta_buckets(8, 2**30)
    tile = msplan.autotune_tile(
        4096, bf, method="bms", backend="vmap", candidates=(256, 1024), trials=1
    )
    assert tile in (256, 1024)
    assert msplan._TILE_CACHE[(4096, 8, "bms", False, "vmap")] == tile
    # subsequent plans pick up the tuned tile
    assert msplan.make_plan(4096, 8, method="bms", backend="vmap", bucket_fn=bf).tile == tile
