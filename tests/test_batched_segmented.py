"""Batched + segmented multisplit acceptance (ISSUE 2): bitwise equivalence
with independent flat calls on every backend, single-launch execution, and
the rewired consumers (segmented_radix_sort, multisplit_all_shards, MoE
segmented routing)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.identifiers import delta_buckets
from repro.core.multisplit import (
    batched_multisplit,
    multisplit,
    multisplit_ref,
    segmented_multisplit,
)
from repro.core.sort import radix_sort, segmented_radix_sort
from repro.core.distributed import multisplit_all_shards
from repro.models import moe

BACKENDS = ["reference", "vmap", "pallas-interpret"]


def _keys(n, seed=0, hi=2**30):
    return jnp.asarray(np.random.RandomState(seed).randint(0, hi, size=n, dtype=np.uint32))


# ---------------------------------------------------------------------------
# Acceptance: bitwise identity with independent calls, every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", ["dms", "wms", "bms"])
def test_segmented_bitwise_identical_to_independent_calls(backend, method):
    """THE acceptance criterion: segmented multisplit over b segments ==
    b independent multisplit calls, bitwise, on every backend."""
    m = 13
    bf = delta_buckets(m, 2**30)
    n = 1400
    keys = _keys(n, seed=3)
    vals = jnp.arange(n, dtype=jnp.int32)
    starts = [0, 211, 211, 650, 1399]            # ragged + empty + size-1 tail
    ends = starts[1:] + [n]
    out = segmented_multisplit(keys, bf, starts, vals, method=method, tile=256, backend=backend)
    for i, (a, e) in enumerate(zip(starts, ends)):
        ind = multisplit(keys[a:e], bf, vals[a:e], method=method, tile=256, backend=backend)
        np.testing.assert_array_equal(np.asarray(out.keys[a:e]), np.asarray(ind.keys))
        np.testing.assert_array_equal(np.asarray(out.values[a:e]), np.asarray(ind.values))
        np.testing.assert_array_equal(
            np.asarray(out.bucket_counts[i]), np.asarray(ind.bucket_counts)
        )
        np.testing.assert_array_equal(
            np.asarray(out.bucket_starts[i]), np.asarray(ind.bucket_starts)
        )
        np.testing.assert_array_equal(
            np.asarray(out.permutation[a:e]), np.asarray(ind.permutation)
        )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("method", ["dms", "wms", "bms"])
def test_batched_bitwise_identical_to_independent_calls(backend, method):
    m, b, n = 13, 6, 700
    bf = delta_buckets(m, 2**30)
    keys = _keys(b * n, seed=5).reshape(b, n)
    vals = jnp.asarray(
        np.random.RandomState(6).randint(0, 2**20, (b, n), dtype=np.int32)
    )
    out = batched_multisplit(keys, bf, vals, method=method, tile=256, backend=backend)
    for i in range(b):
        ind = multisplit(keys[i], bf, vals[i], method=method, tile=256, backend=backend)
        np.testing.assert_array_equal(np.asarray(out.keys[i]), np.asarray(ind.keys))
        np.testing.assert_array_equal(np.asarray(out.values[i]), np.asarray(ind.values))
        np.testing.assert_array_equal(
            np.asarray(out.bucket_counts[i]), np.asarray(ind.bucket_counts)
        )
        np.testing.assert_array_equal(
            np.asarray(out.permutation[i]), np.asarray(ind.permutation)
        )


# ---------------------------------------------------------------------------
# Single launch: the whole batch / all segments go through ONE kernel-grid
# entry-point invocation, not one per row/segment
# ---------------------------------------------------------------------------

def _count_calls(monkeypatch, module, name):
    calls = []
    orig = getattr(module, name)

    def spy(*a, **k):
        calls.append(name)
        return orig(*a, **k)

    monkeypatch.setattr(module, name, spy)
    return calls


def test_batched_pallas_is_one_grid_launch(monkeypatch):
    from repro.kernels import ops as kops

    # delta specs are label-fused since PR-4: count the spec entry points.
    # m=4 keeps the dense one-hot family (PACKED_MIN_BUCKETS=8 since ISSUE 6)
    # so the dense entry points spied on below are the ones dispatched.
    pre = _count_calls(monkeypatch, kops, "spec_tile_histograms")
    post = _count_calls(monkeypatch, kops, "spec_fused_postscan_reorder")
    b, n = 8, 512
    keys = _keys(b * n, seed=7).reshape(b, n)
    bf = delta_buckets(4, 2**30)
    out = batched_multisplit(keys, bf, tile=256, backend="pallas-interpret")
    assert len(pre) == 1 and len(post) == 1       # 8 rows, ONE launch each stage
    ref = multisplit_ref(keys.reshape(-1)[:n], bf)
    np.testing.assert_array_equal(np.asarray(out.keys[0]), np.asarray(ref.keys))


def test_segmented_pallas_is_one_grid_launch(monkeypatch):
    from repro.kernels import ops as kops

    # the combined seg width (5 segments x 4 buckets = 20 >= 8) selects the
    # PACKED family since ISSUE 6, whose generic kernels cover flat AND
    # segmented in the same entry points
    pre = _count_calls(monkeypatch, kops, "packed_tile_histograms")
    post = _count_calls(monkeypatch, kops, "packed_fused_postscan_reorder")
    keys = _keys(1000, seed=8)
    bf = delta_buckets(4, 2**30)
    segmented_multisplit(keys, bf, [0, 100, 400, 400, 900], tile=256, backend="pallas-interpret")
    assert len(pre) == 1 and len(post) == 1       # 5 ragged segments, ONE launch


def test_segmented_radix_sort_pallas_never_materializes_labels(monkeypatch):
    """The fused-digit guarantee extends to the segmented path: no bucket
    spec is ever evaluated host-side."""
    from repro.core import identifiers

    calls = []
    orig = identifiers.BucketSpec.__call__

    def spy(self, keys):
        calls.append(self.name)
        return orig(self, keys)

    monkeypatch.setattr(identifiers.BucketSpec, "__call__", spy)
    keys = _keys(900, seed=9, hi=2**32)
    vals = jnp.arange(900, dtype=jnp.int32)
    starts = [0, 300, 300, 500]
    ks, vs = segmented_radix_sort(
        keys, starts, vals, radix_bits=4, backend="pallas-interpret", tile=256
    )
    assert calls == [], f"host-side label materialization via {calls}"
    ends = starts[1:] + [900]
    for a, e in zip(starts, ends):
        order = np.argsort(np.asarray(keys[a:e]), kind="stable")
        np.testing.assert_array_equal(np.asarray(ks[a:e]), np.asarray(keys[a:e])[order])
        np.testing.assert_array_equal(np.asarray(vs[a:e]), np.asarray(vals[a:e])[order])


# ---------------------------------------------------------------------------
# Consumers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_segmented_radix_sort_vs_per_segment_radix_sort(backend):
    """One segmented pass sequence == radix_sort on each segment slice."""
    keys = _keys(800, seed=10, hi=2**32)
    starts = [0, 123, 456, 456]
    ends = starts[1:] + [800]
    ks, _ = segmented_radix_sort(keys, starts, radix_bits=8, tile=256, backend=backend)
    for a, e in zip(starts, ends):
        ind, _ = radix_sort(keys[a:e], radix_bits=8, tile=256, backend=backend)
        np.testing.assert_array_equal(np.asarray(ks[a:e]), np.asarray(ind))


@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_multisplit_all_shards_matches_global_oracle(backend):
    """The device-level local stage as ONE batched plan: global result ==
    stable multisplit of the concatenated shards."""
    d, n = 4, 600
    bf = delta_buckets(16, 2**30)
    keys = _keys(d * n, seed=12).reshape(d, n)
    vals = jnp.arange(d * n, dtype=jnp.int32).reshape(d, n)
    out = multisplit_all_shards(keys, bf, vals, tile=256, backend=backend)
    ref = multisplit_ref(keys.reshape(-1), bf, vals.reshape(-1))
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(ref.keys))
    np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref.values))
    np.testing.assert_array_equal(np.asarray(out.bucket_counts), np.asarray(ref.bucket_counts))
    np.testing.assert_array_equal(np.asarray(out.bucket_starts), np.asarray(ref.bucket_starts))
    np.testing.assert_array_equal(np.asarray(out.permutation), np.asarray(ref.permutation))


def test_multisplit_all_shards_local_stage_is_one_batched_launch(monkeypatch):
    from repro.kernels import ops as kops

    post = _count_calls(monkeypatch, kops, "spec_fused_postscan_reorder")
    keys = _keys(4 * 512, seed=13).reshape(4, 512)
    # m=4 sits below PACKED_MIN_BUCKETS=8 (ISSUE 6), keeping the dense
    # entry point spied on above as the dispatched one
    bf = delta_buckets(4, 2**30)
    multisplit_all_shards(keys, bf, tile=256, backend="pallas-interpret")
    assert len(post) == 1                         # 4 shards, ONE local-stage launch


def test_moe_segmented_ranks_match_per_segment():
    """Token routing as ONE segmented multisplit call: per-request ranks and
    per-request expert loads equal independent per-request routing."""
    rng = np.random.RandomState(14)
    ids = jnp.asarray(rng.randint(0, 8, 4096, dtype=np.int32))
    starts = [0, 1024, 1024, 3000]
    ends = starts[1:] + [4096]
    r_seg, c_seg = moe._ranks_multisplit(ids, 8, segment_starts=starts)
    assert c_seg.shape == (4, 8)
    for i, (a, e) in enumerate(zip(starts, ends)):
        r_i, c_i = moe._ranks_multisplit(ids[a:e], 8)
        np.testing.assert_array_equal(np.asarray(r_seg[a:e]), np.asarray(r_i))
        np.testing.assert_array_equal(np.asarray(c_seg[i]), np.asarray(c_i))
    # the sort oracle agrees segment-by-segment too
    for a, e in zip(starts, ends):
        r_srt, _ = moe._ranks_sort(ids[a:e], 8)
        np.testing.assert_array_equal(np.asarray(r_seg[a:e]), np.asarray(r_srt))


def test_moe_route_tokens_segmented_slots():
    """Kept slots are unique, capacity-bounded and stable per (request,
    expert); dropped tokens are exactly the over-capacity tail."""
    rng = np.random.RandomState(15)
    e, cap = 4, 8
    ids = jnp.asarray(rng.randint(0, e, 400, dtype=np.int32))
    starts = [0, 100, 100, 280]
    slot, keep, counts = moe.route_tokens_segmented(ids, starts, e, cap)
    slot_np, keep_np = np.asarray(slot), np.asarray(keep)
    kept = slot_np[keep_np]
    assert len(set(kept.tolist())) == kept.size          # unique dispatch slots
    assert (slot_np[~keep_np] == len(starts) * e * cap).all()
    # per (segment, expert): kept count == min(load, cap)
    counts_np = np.asarray(counts)
    ends = starts[1:] + [400]
    ids_np = np.asarray(ids)
    for i, (a, b) in enumerate(zip(starts, ends)):
        for ex in range(e):
            load = int((ids_np[a:b] == ex).sum())
            assert counts_np[i, ex] == load
            in_block = (kept // cap == i * e + ex).sum()
            assert in_block == min(load, cap)


def test_moe_block_unchanged_by_plan_routing():
    """The flat routing rewrite (hand-rolled pipeline -> one plan call) must
    not change moe_block outputs vs the stable-sort oracle."""
    import dataclasses
    import jax
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.parallel.sharding import init_params

    cfg = ModelConfig(
        name="t", family="moe", n_layers=2, d_model=32, n_heads=4, n_kv=4,
        d_ff=64, vocab=64, dtype="float32",
        moe=MoEConfig(num_experts=8, top_k=2, dispatch="multisplit", capacity_factor=1.0),
    )
    params = init_params(moe.moe_decl(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32), jnp.float32)
    y_ms, aux_ms = moe.moe_block(params, x, cfg)
    y_srt, aux_srt = moe.moe_block(
        params, x, dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, dispatch="sort"))
    )
    np.testing.assert_array_equal(np.asarray(y_ms), np.asarray(y_srt))
    assert float(aux_ms.drop_fraction) == float(aux_srt.drop_fraction)
