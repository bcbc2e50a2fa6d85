"""Gather-free compiled-path kernels (DESIGN.md §15).

Three proof obligations, none of which needs a TPU:

1. **The jaxpr lint** — every Pallas kernel entry point, traced with its
   compiled-path (oblivious) defaults, contains no gather/scatter/tensor-
   indexed-slice primitive inside any ``pallas_call`` body; and the lint
   itself is trustworthy because it FAILS on fixture kernels that
   deliberately gather and scatter.
2. **Bitwise identity** — the oblivious bodies (one-hot selects, 16-bit
   rank planes, permutation matmuls) return exactly the arrays the gather
   forms return, across families × layouts × digit splits × key-value.
3. **Dispatch** — ``pallas`` means compiled-when-available:
   ``Backend.compiled`` × TPU presence × ``REPRO_INTERPRET`` resolve the
   per-call ``interpret`` flag; ``pallas-interpret`` stays pinned.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.identifiers import BitfieldSpec, RangeSpec
from repro.core.pipeline import get_backend
from repro.kernels import lint as klint
from repro.kernels import multisplit_tile as mst
from repro.kernels import ops as kops
from repro.kernels.common import (
    _dense_local_offsets,
    fused2_counts_body,
    fused2_postscan_body,
    packed_layout,
    packed_local_offsets,
    packed_positions_body,
    packed_postscan_body,
)


def _ids(t, m, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, m, t, dtype=np.int32))


def _keys(t, seed=0, hi=2**32):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, hi, t, dtype=np.uint64).astype(np.uint32)
    )


# ---------------------------------------------------------------------------
# 1a. The lint passes on every registered entry point
# ---------------------------------------------------------------------------

_ENTRY_POINTS = sorted(klint.kernel_entry_points())


@pytest.mark.parametrize("name", _ENTRY_POINTS)
def test_lint_entry_point_is_gather_free(name):
    r = klint.kernel_entry_points()[name]()
    assert r.pallas_calls >= 1, f"{name}: no pallas_call traced"
    assert not r.violations, f"{name}: forbidden primitives {r.violations}"


def test_lint_registry_covers_every_family():
    prefixes = {n.split("/")[0] for n in _ENTRY_POINTS}
    assert {"dense", "seg", "spec", "seg_spec", "packed", "fused2",
            "radix", "seg_radix"} <= prefixes


def test_lint_report_lists_primitives():
    rep = klint.lint_report()
    assert "dense/histograms" in rep and "fused2/fused_kv_packed" in rep
    assert "FORBIDDEN" not in rep


# ---------------------------------------------------------------------------
# 1b. The lint FAILS on kernels that really gather / scatter (satellite 3)
# ---------------------------------------------------------------------------

def _gather_fixture_kernel(ids_ref, incl_ref, out_ref):
    ids = ids_ref[0, :]
    incl = incl_ref[0, :]
    out_ref[0, :] = jnp.take_along_axis(incl, ids, axis=0)


def _scatter_fixture_kernel(ids_ref, keys_ref, out_ref):
    ids = ids_ref[0, :]
    out_ref[0, :] = jnp.zeros_like(keys_ref[0, :]).at[ids].set(keys_ref[0, :])


def _fixture_call(kernel, *args):
    t = args[0].shape[1]
    row = pl.BlockSpec((1, t), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(args[0].shape[0],),
        in_specs=[row] * len(args),
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(args[0].shape, args[-1].dtype),
        interpret=True,
    )(*args)


def test_lint_catches_in_kernel_gather():
    ids = jnp.zeros((1, 128), jnp.int32)
    r = klint.lint_fn(
        lambda i, x: _fixture_call(_gather_fixture_kernel, i, x),
        ids, jnp.zeros((1, 128), jnp.int32), name="fixture/gather",
    )
    assert r.pallas_calls == 1
    assert "gather" in r.violations


def test_lint_catches_in_kernel_scatter():
    ids = jnp.zeros((1, 128), jnp.int32)
    r = klint.lint_fn(
        lambda i, k: _fixture_call(_scatter_fixture_kernel, i, k),
        ids, jnp.zeros((1, 128), jnp.uint32), name="fixture/scatter",
    )
    assert r.pallas_calls == 1
    assert any(v.startswith("scatter") for v in r.violations)


def test_lint_ignores_host_side_gathers():
    # gathers OUTSIDE pallas_call are the legitimate host path: not flagged
    def host_gather_then_kernel(i):
        g = jnp.cumsum(jnp.ones(16, jnp.int32))[i[0, :16] % 16]  # host gather
        h = mst.tile_histograms_pallas(i, 16)
        return h, g

    r = klint.lint_fn(host_gather_then_kernel, jnp.zeros((1, 128), jnp.int32),
                      name="fixture/host-gather")
    assert r.pallas_calls == 1 and not r.violations


# ---------------------------------------------------------------------------
# 2. Bitwise identity: oblivious bodies == gather bodies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,m,bits", [
    (128, 8, 8), (256, 256, 8), (512, 37, 4), (96, 16, 8), (1024, 256, 8),
])
def test_packed_local_offsets_oblivious_bitwise(t, m, bits):
    lay_g = packed_layout(t, m, bits=bits)
    lay_o = packed_layout(t, m, bits=bits, rank16=True)
    ids = _ids(t, m, seed=t + m)
    lg, hg = packed_local_offsets(ids, lay_g, oblivious=False)
    lo, ho = packed_local_offsets(ids, lay_o, oblivious=True)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lo))
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(ho))


def test_packed_local_offsets_oblivious_adversarial_saturation():
    # all-one-bucket strip maxes the subword counters AND the rank planes
    lay = packed_layout(1024, 256, rank16=True)
    ids = jnp.zeros((1024,), jnp.int32)
    lg, hg = packed_local_offsets(ids, packed_layout(1024, 256), oblivious=False)
    lo, ho = packed_local_offsets(ids, lay, oblivious=True)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lo))
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(ho))


@pytest.mark.parametrize("t,m", [(256, 16), (512, 256)])
def test_packed_positions_and_postscan_oblivious_bitwise(t, m):
    ids = _ids(t, m, seed=3)
    keys = _keys(t, seed=4)
    vals = jnp.arange(t, dtype=jnp.uint32)
    g_row = jnp.asarray(np.random.RandomState(5).randint(0, 1 << 20, m, dtype=np.int32))
    lay_g = packed_layout(t, m)
    lay_o = packed_layout(t, m, rank16=True)
    pg = packed_positions_body(ids, g_row, lay_g, oblivious=False)
    po = packed_positions_body(ids, g_row, lay_o, oblivious=True)
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(po))
    for v in (vals, None):
        outs_g = packed_postscan_body(ids, g_row, keys, v, lay_g, oblivious=False)
        outs_o = packed_postscan_body(ids, g_row, keys, v, lay_o, oblivious=True)
        for a, b in zip(outs_g, outs_o):
            if a is None:
                assert b is None
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("t,m", [(256, 16), (128, 100)])
def test_dense_local_offsets_oblivious_bitwise(t, m):
    ids = _ids(t, m, seed=9)
    lg, hg = _dense_local_offsets(ids, m, oblivious=False)
    lo, ho = _dense_local_offsets(ids, m, oblivious=True)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lo))
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(ho))


@pytest.mark.parametrize("bits,num_segments", [(8, 1), (8, 4), (6, 1), (4, 3)])
def test_fused2_counts_oblivious_bitwise(bits, num_segments):
    t = 256
    keys = _keys(t, seed=bits)
    seg = None
    if num_segments > 1:
        seg = jnp.sort(jnp.asarray(
            np.random.RandomState(7).randint(0, num_segments, t, dtype=np.int32)))
    hg = fused2_counts_body(keys, 0, bits, seg=seg, num_segments=num_segments,
                            oblivious=False)
    ho = fused2_counts_body(keys, 0, bits, seg=seg, num_segments=num_segments,
                            oblivious=True)
    np.testing.assert_array_equal(np.asarray(hg), np.asarray(ho))


@pytest.mark.parametrize("t,bits,split,family,num_segments,kv", [
    (256, 8, 4, "onehot", 1, True),
    (256, 8, 4, "packed", 1, True),
    (256, 6, 3, "onehot", 4, False),
    (512, 8, 5, "packed", 3, True),      # asymmetric digit_split
    (128, 4, 2, "onehot", 1, False),
])
def test_fused2_postscan_oblivious_bitwise(t, bits, split, family, num_segments, kv):
    keys = _keys(t, seed=t + bits)
    vals = jnp.arange(t, dtype=jnp.uint32) if kv else None
    seg = None
    if num_segments > 1:
        seg = jnp.sort(jnp.asarray(
            np.random.RandomState(2).randint(0, num_segments, t, dtype=np.int32)))
    m_eff = (1 << bits) * num_segments
    g_row = jnp.asarray(
        np.random.RandomState(6).randint(0, 1 << 20, m_eff, dtype=np.int32))
    kw = dict(seg=seg, num_segments=num_segments, family=family)
    outs_g = fused2_postscan_body(keys, g_row, vals, 0, split, bits,
                                  oblivious=False, **kw)
    outs_o = fused2_postscan_body(keys, g_row, vals, 0, split, bits,
                                  oblivious=True, **kw)
    for a, b in zip(outs_g, outs_o):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_wrappers_oblivious_matches_gather_end_to_end():
    """Through the actual pallas_call doors (interpret), both flag values."""
    t, m = 256, 16
    ids = jnp.stack([_ids(t, m, seed=s) for s in (0, 1)])
    g = jnp.asarray(np.random.RandomState(3).randint(0, 1 << 20, (2, m), dtype=np.int32))
    pg = mst.packed_tile_positions_pallas(ids, g, m, oblivious=False)
    po = mst.packed_tile_positions_pallas(ids, g, m, oblivious=True)
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(po))

    pair = BitfieldSpec(0, 8)
    keys = jnp.stack([_keys(t, seed=s) for s in (4, 5)])
    vals = jnp.stack([jnp.arange(t, dtype=jnp.uint32)] * 2)
    gp = jnp.asarray(np.random.RandomState(8).randint(0, 1 << 20, (2, 256), dtype=np.int32))
    outs_g = mst.fused2_fused_postscan_reorder_pallas(
        keys, gp, vals, spec=pair, split=4, oblivious=False)
    outs_o = mst.fused2_fused_postscan_reorder_pallas(
        keys, gp, vals, spec=pair, split=4, oblivious=True)
    for a, b in zip(outs_g, outs_o):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 2b. The rank16 overflow guard (satellite 6)
# ---------------------------------------------------------------------------

def test_packed_layout_rank16_guard_rejects_big_tiles():
    # two 16-bit ranks per int32 lane: a rank can reach tile, so tile > 2^16-1
    # must be rejected AT LAYOUT TIME when the oblivious body will run
    with pytest.raises(ValueError, match="rank"):
        packed_layout(1 << 17, 16, rank16=True)
    # the boundary tile is legal ...
    assert packed_layout(0xFFFF, 16, rank16=True).tile == 0xFFFF
    # ... and the gather path keeps accepting big tiles (the vmap oracle)
    assert packed_layout(1 << 17, 16).tile == 1 << 17


def test_packed_local_offsets_oblivious_runtime_guard():
    # a layout built WITHOUT rank16 must still refuse the oblivious body
    lay = packed_layout(1 << 17, 16)
    ids = jnp.zeros((1 << 17,), jnp.int32)
    with pytest.raises(ValueError, match="rank"):
        packed_local_offsets(ids, lay, oblivious=True)


# ---------------------------------------------------------------------------
# 3. RangeSpec: balanced-tree emit == host emit == searchsorted (sat. 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 2, 3, 31, 255])
@pytest.mark.parametrize("dtype", [np.uint32, np.int32])
def test_rangespec_tree_matches_chain_and_searchsorted(s, dtype):
    rng = np.random.RandomState(s)
    splitters = np.unique(rng.randint(0, 1 << 30, s).astype(dtype))
    spec = RangeSpec(tuple(splitters.tolist()))
    keys_np = rng.randint(0, 1 << 30, 4096).astype(dtype)
    keys_np[:s] = splitters[: min(s, 4096)]         # exact splitter hits
    keys = jnp.asarray(keys_np)
    tree = np.asarray(spec.emit_in_kernel(keys))
    host = np.asarray(spec.emit(keys))
    ref = np.searchsorted(splitters, keys_np, side="right")
    np.testing.assert_array_equal(tree, host)
    np.testing.assert_array_equal(tree, ref)


def test_rangespec_tree_traces_log_depth_adds():
    # s=255 splitters: 255 ge-compares but only ~s adds in a log-depth tree;
    # the WHOLE kernel jaxpr stays free of gathers (linted above) and small
    spec = RangeSpec(tuple(range(1, 256)))
    jx = jax.make_jaxpr(spec.emit_in_kernel)(jnp.zeros((128,), jnp.uint32))
    names = [e.primitive.name for e in jx.jaxpr.eqns]
    assert names.count("ge") == 255
    assert "gather" not in names and "scatter" not in names


# ---------------------------------------------------------------------------
# 4. Interpret resolution: Backend.compiled × TPU × REPRO_INTERPRET
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _fresh_tpu_probe():
    kops._tpu_available.cache_clear()
    yield
    kops._tpu_available.cache_clear()


def test_resolve_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    assert kops.resolve_interpret(True) is True
    assert kops.resolve_interpret(False) is True
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert kops.resolve_interpret(True) is False
    assert kops.resolve_interpret(False) is False


def test_resolve_interpret_defaults_follow_tpu_presence(monkeypatch):
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    monkeypatch.setattr(kops, "_tpu_available", lambda: False)
    assert kops.resolve_interpret(True) is True       # no TPU -> interpret
    assert kops.resolve_interpret(False) is True
    monkeypatch.setattr(kops, "_tpu_available", lambda: True)
    assert kops.resolve_interpret(True) is False      # compiled target + TPU
    assert kops.resolve_interpret(False) is True      # debug target pinned


def test_backend_compiled_capability():
    assert get_backend("pallas").compiled
    assert not get_backend("pallas-interpret").compiled
    assert not get_backend("vmap").compiled
    # the dynamic property consults the resolver every time
    assert get_backend("pallas").stages.interpret == kops.resolve_interpret(True)
    assert get_backend("pallas-interpret").stages.interpret is True


def test_repro_interpret_env_reaches_backend_stages(monkeypatch):
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert get_backend("pallas").stages.interpret is False
    assert get_backend("pallas-interpret").stages.interpret is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    assert get_backend("pallas").stages.interpret is True
