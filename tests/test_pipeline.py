"""The stage-graph pipeline package (ISSUE 3): backend registry, partial
pipelines (counts_only / positions_only), and the chained RadixPipeline
(pad/tile exactly once per sort)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import pipeline as pl
from repro.core.identifiers import delta_buckets
from repro.core.multisplit import (
    batched_multisplit,
    multisplit,
    multisplit_ref,
    segmented_multisplit,
)
from repro.core.pipeline import RadixPipeline, get_backend, make_plan
from repro.core.sort import radix_sort, segmented_radix_sort

BACKENDS = ["reference", "vmap", "pallas-interpret"]


def _keys(n, seed=0, hi=2**30):
    return jnp.asarray(np.random.RandomState(seed).randint(0, hi, size=n, dtype=np.uint32))


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

def test_registry_knows_all_four_backends():
    names = pl.backend_names()
    assert names == ("reference", "vmap", "pallas-interpret", "pallas")
    assert pl.BACKENDS == names
    for b in pl.available_backends():
        assert b.description


def test_registry_capability_flags():
    assert not get_backend("reference").tiled
    assert get_backend("vmap").tiled and not get_backend("vmap").uses_kernels
    for name in ("pallas-interpret", "pallas"):
        b = get_backend(name)
        assert b.uses_kernels and b.fuses_radix and b.key_itemsize == 4
    # 'pallas' is COMPILED-when-available: interpret resolves dynamically
    # from Backend.compiled × TPU presence × REPRO_INTERPRET (DESIGN.md §15).
    assert not get_backend("pallas-interpret").compiled
    assert get_backend("pallas").compiled
    from repro.kernels import ops as kops
    assert get_backend("pallas-interpret").stages.interpret is True
    assert get_backend("pallas").stages.interpret == kops.resolve_interpret(True)


def test_registry_rejects_unknown_and_duplicate():
    with pytest.raises(ValueError):
        get_backend("cuda")
    with pytest.raises(ValueError):
        pl.make_plan(100, 4, backend="cuda")
    with pytest.raises(ValueError):
        pl.register_backend(pl.Backend(name="vmap", description="dup"))


def test_registry_extension_is_one_call():
    """A new execution target is one register_backend call: plans resolve and
    run through it with zero changes anywhere else."""
    pl.register_backend(pl.Backend(
        name="vmap-twin", description="test-only clone", stages=pl.VmapStages()
    ))
    try:
        bf = delta_buckets(8, 2**30)
        keys = _keys(500, seed=1)
        out = make_plan(500, 8, backend="vmap-twin", bucket_fn=bf, tile=128)(keys)
        ref = multisplit_ref(keys, bf)
        np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    finally:
        del pl.registry._REGISTRY["vmap-twin"]


# ---------------------------------------------------------------------------
# PipelineSpec: modes, validation, stage graph
# ---------------------------------------------------------------------------

def test_mode_validation():
    with pytest.raises(ValueError):
        make_plan(100, 4, mode="sideways")
    with pytest.raises(ValueError):                  # partial modes are key-only
        make_plan(100, 4, mode="counts_only", key_value=True)
    p = make_plan(100, 4, bucket_fn=delta_buckets(4), mode="counts_only")
    with pytest.raises(ValueError):                  # resolved key-only
        p(_keys(100), jnp.arange(100))


def test_stage_graph_per_mode():
    # m=4 < PACKED_MIN_BUCKETS keeps the stage names family-tag-free
    bf = delta_buckets(4)
    full = make_plan(1024, 4, method="bms", backend="vmap", bucket_fn=bf)
    co = make_plan(1024, 4, method="bms", backend="vmap", bucket_fn=bf,
                   mode="counts_only")
    po = make_plan(1024, 4, method="bms", backend="pallas-interpret",
                   bucket_fn=bf, mode="positions_only")
    assert full.stages() == (
        "prescan:vmap", "scan:global", "postscan:fused-reorder-vmap",
        "scatter:bucket-major",
    )
    assert co.stages() == ("prescan:vmap", "reduce:counts")
    assert po.stages() == (           # fusable spec on a kernel backend (PR-4)
        "prescan:fused-label-kernel", "scan:global",
        "postscan:fused-label-positions-kernel",
    )
    assert [s.name for s in co.stage_graph()] == ["prescan", "reduce"]
    assert co.stage_graph()[0].impl == "vmap"
    seg = make_plan(1024, 4, bucket_fn=bf, segments=4, mode="counts_only")
    assert seg.stage_graph()[0].name == "layout"


def test_counts_only_empty_and_layout_shapes():
    bf = delta_buckets(4)
    for backend in BACKENDS:
        flat = make_plan(0, 4, backend=backend, bucket_fn=bf, mode="counts_only")(_keys(0))
        assert flat.keys is None and flat.permutation is None
        np.testing.assert_array_equal(np.asarray(flat.bucket_counts), np.zeros(4))
        bt = make_plan(0, 4, backend=backend, bucket_fn=bf, batch=3,
                       mode="counts_only")(_keys(0).reshape(3, 0))
        assert bt.bucket_counts.shape == (3, 4)


def test_partial_modes_non_32bit_keys_on_kernel_backend():
    """Non-fused partial modes never feed keys to a kernel (only int32 ids),
    so non-32-bit key dtypes stay usable — the histogram consumer's float
    path and any positions-only bucketing over narrow keys. The full reorder
    (keys DO enter the kernel) still enforces the 32-bit-lane restriction."""
    from repro.core.identifiers import from_fn

    keys = jnp.asarray(np.random.RandomState(0).randint(0, 8, 5000, dtype=np.uint16))
    bf = from_fn(lambda u: u.astype(jnp.int32), 8, name="u16-identity")
    out = multisplit(keys, bf, tile=256, backend="pallas-interpret", mode="counts_only")
    np.testing.assert_array_equal(
        np.asarray(out.bucket_counts), np.bincount(np.asarray(keys), minlength=8)
    )
    po = multisplit(keys, bf, tile=256, backend="pallas-interpret", mode="positions_only")
    ref = multisplit_ref(keys, bf)
    np.testing.assert_array_equal(np.asarray(po.permutation), np.asarray(ref.permutation))
    with pytest.raises(ValueError):                  # the full reorder still checks
        multisplit(keys, bf, tile=256, backend="pallas-interpret")


# ---------------------------------------------------------------------------
# RadixPipeline: chained passes, bitwise identity, pad/tile exactly once
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", ["dms", "bms"])
@pytest.mark.parametrize("key_value", [False, True])
def test_chained_radix_bitwise_matches_per_pass(backend, method, key_value):
    """THE acceptance criterion: radix_sort (chained RadixPipeline) is
    bitwise identical to a stable sort of the keys on every backend."""
    rng = np.random.RandomState(7)
    keys = jnp.asarray(rng.randint(0, 2**32, 2500 + 13, dtype=np.uint32))
    vals = jnp.arange(keys.shape[0], dtype=jnp.int32) if key_value else None
    ks, vs = radix_sort(keys, vals, radix_bits=8, method=method, backend=backend, tile=512)
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(keys)[order])
    if key_value:
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(vals)[order])


@pytest.mark.parametrize("backend", BACKENDS)
def test_chained_batched_radix_matches_per_pass(backend):
    rng = np.random.RandomState(3)
    keys = jnp.asarray(rng.randint(0, 2**16, (5, 700), dtype=np.uint32))
    ks, _ = radix_sort(keys, radix_bits=4, key_bits=16, backend=backend, tile=128)
    order = np.argsort(np.asarray(keys), axis=1, kind="stable")
    np.testing.assert_array_equal(
        np.asarray(ks), np.take_along_axis(np.asarray(keys), order, axis=1))


@pytest.mark.parametrize("backend", BACKENDS)
def test_chained_segmented_radix_matches_per_pass(backend):
    rng = np.random.RandomState(5)
    keys = jnp.asarray(rng.randint(0, 2**16, 900, dtype=np.uint32))
    starts = [0, 0, 300, 650]                        # empty first segment
    ks, _ = segmented_radix_sort(
        keys, starts, radix_bits=4, key_bits=16, backend=backend, tile=128
    )
    for a, e in zip(starts, starts[1:] + [900]):
        seg = np.asarray(keys[a:e])
        np.testing.assert_array_equal(
            np.asarray(ks[a:e]), seg[np.argsort(seg, kind="stable")]
        )


def _count_padding(monkeypatch):
    from repro.core.pipeline import stages as st

    calls = {"pad_to_tiles": 0, "pad_rows": 0}
    orig_pt, orig_pr = st.pad_to_tiles, st.pad_rows

    def count_pt(x, tile, fill):
        calls["pad_to_tiles"] += 1
        return orig_pt(x, tile, fill)

    def count_pr(x, n_row, fill):
        calls["pad_rows"] += 1
        return orig_pr(x, n_row, fill)

    monkeypatch.setattr(st, "pad_to_tiles", count_pt)
    monkeypatch.setattr(st, "pad_rows", count_pr)
    return calls


@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_radix_pipeline_pads_and_tiles_exactly_once(backend, monkeypatch):
    """Acceptance: the chained pipeline pads/tiles each operand ONCE per
    sort, not once per digit pass."""
    calls = _count_padding(monkeypatch)
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 2**32, 3000, dtype=np.uint32))
    vals = jnp.arange(3000, dtype=jnp.int32)

    ks, vs = radix_sort(keys, vals, radix_bits=8, backend=backend, tile=512)
    assert calls["pad_to_tiles"] == 2                # keys once + values once
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(keys)[order])
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(vals)[order])


def test_segmented_radix_pipeline_pads_once(monkeypatch):
    calls = _count_padding(monkeypatch)
    keys = _keys(900, seed=2, hi=2**16)
    segmented_radix_sort(keys, [0, 300, 650], radix_bits=4, key_bits=16, tile=128)
    # keys once + the position-keyed segment-id buffer once (key-only sort)
    assert calls["pad_to_tiles"] == 2


def test_batched_radix_pipeline_pads_rows_once(monkeypatch):
    calls = _count_padding(monkeypatch)
    keys = _keys(4 * 700, seed=4, hi=2**16).reshape(4, 700)
    radix_sort(keys, radix_bits=4, key_bits=16, tile=128)
    assert calls["pad_rows"] == 1 and calls["pad_to_tiles"] == 0


def test_radix_pipeline_resolves_tile_once():
    """All per-pass plans share ONE resolved tile (no per-pass re-resolution
    drift, even when the final pass has a narrower digit)."""
    rp = RadixPipeline(100_000, radix_bits=7, key_bits=32, backend="vmap")
    assert rp.n_passes == 5
    assert len({p.tile for p in rp.plans}) == 1
    assert rp.plans[-1].radix == (28, 4)             # 5th pass covers 4 bits


# ---------------------------------------------------------------------------
# Layering
# ---------------------------------------------------------------------------

def test_no_private_cross_module_reaches_in_consumers():
    """Acceptance: migrated consumers are grep-clean of private plan-layer
    reaches (the old ``ms._pad_to_tiles`` / ``HIST_TILE`` layering bug)."""
    import inspect

    from repro.core import distributed, histogram, sort
    from repro.data import pipeline as data_pipeline
    from repro.models import moe

    for mod in (histogram, sort, distributed, moe, data_pipeline):
        src = inspect.getsource(mod)
        assert "ms._pad_to_tiles" not in src, mod.__name__
        assert "HIST_TILE" not in src, mod.__name__
        assert "plan._" not in src, mod.__name__
        assert "pipeline._" not in src.replace("data_pipeline._", ""), mod.__name__


# ---------------------------------------------------------------------------
# Partial-pipeline consumers
# ---------------------------------------------------------------------------

def test_histogram_is_counts_only_pipeline(monkeypatch):
    """histogram() must not run scan/postscan/scatter: reorder and positions
    stage entry points stay untouched."""
    from repro.core.histogram import histogram_even
    from repro.core.pipeline.registry import KernelStages, VmapStages

    def boom(*a, **k):
        raise AssertionError("counts_only pipeline ran a post-prescan stage")

    for cls in (KernelStages, VmapStages):
        monkeypatch.setattr(cls, "positions", boom)
        monkeypatch.setattr(cls, "reorder", boom)
    keys = jnp.asarray(np.random.RandomState(1).uniform(0, 64, 9000).astype(np.float32))
    for backend in ("vmap", "pallas-interpret"):
        h = histogram_even(keys, 0.0, 64.0, 16, backend=backend)
        expect, _ = np.histogram(np.asarray(keys), bins=16, range=(0, 64))
        np.testing.assert_array_equal(np.asarray(h), expect)


def test_moe_expert_load_stats_counts_only():
    from repro.models.moe import expert_load_stats

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 8, 1000, dtype=np.int32))
    counts, overflow = expert_load_stats(ids, 8, capacity=100)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(ids), minlength=8)
    )
    expect_drop = np.maximum(np.bincount(np.asarray(ids), minlength=8) - 100, 0).sum() / 1000
    assert abs(float(overflow) - expect_drop) < 1e-6
    # segmented: per-request load in one call
    starts = jnp.asarray([0, 400, 400], jnp.int32)
    seg_counts, _ = expert_load_stats(ids, 8, segment_starts=starts)
    assert seg_counts.shape == (3, 8)
    np.testing.assert_array_equal(
        np.asarray(seg_counts[0]), np.bincount(np.asarray(ids[:400]), minlength=8)
    )
    np.testing.assert_array_equal(np.asarray(seg_counts[1]), np.zeros(8))


def test_data_pipeline_bucket_orders_segmented():
    """batches_at buckets every step's lengths in ONE segmented launch and is
    bitwise identical to independent batch_at calls."""
    from repro.data import DataPipeline

    p = DataPipeline(vocab=256, seq_len=128, batch_per_host=2, seed=7)
    expect = [p.batch_at(5 + i) for i in range(3)]
    got = p.batches_at(5, 3)
    for e, g in zip(expect, got):
        np.testing.assert_array_equal(e["tokens"], g["tokens"])
        np.testing.assert_array_equal(e["labels"], g["labels"])
