"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SWEEP = [
    (1, 128, 2), (4, 256, 8), (2, 1024, 32), (3, 512, 256), (2, 384, 13),
]


@pytest.mark.parametrize("L,T,m", SWEEP)
def test_tile_histograms(L, T, m):
    ids = jnp.asarray(np.random.RandomState(L * T).randint(0, m, (L, T), dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.tile_histograms(ids, m)), np.asarray(ref.tile_histograms(ids, m))
    )


@pytest.mark.parametrize("L,T,m", SWEEP)
def test_tile_positions(L, T, m):
    rng = np.random.RandomState(m)
    ids = jnp.asarray(rng.randint(0, m, (L, T), dtype=np.int32))
    g = jnp.asarray(rng.randint(0, 100000, (L, m), dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.tile_positions(ids, g, m)),
        np.asarray(ref.tile_positions(ids, g, m)),
    )


@pytest.mark.parametrize("L,T,m", SWEEP)
@pytest.mark.parametrize("key_value", [False, True])
@pytest.mark.parametrize("kdtype", [np.uint32, np.int32])
def test_fused_postscan_reorder(L, T, m, key_value, kdtype):
    """THE fused kernel == composition of positions + reorder oracles."""
    rng = np.random.RandomState(L * T + m)
    ids = jnp.asarray(rng.randint(0, m, (L, T), dtype=np.int32))
    keys = jnp.asarray(rng.randint(0, 2**31 - 1, (L, T)).astype(kdtype))
    vals = jnp.asarray(rng.randint(0, 2**31 - 1, (L, T), dtype=np.int32)) if key_value else None
    g = jnp.asarray(rng.randint(0, 100000, (L, m), dtype=np.int32))
    kk, vk, pk, permk = ops.fused_postscan_reorder(ids, g, keys, vals, m)
    kr, vr, pr, permr = ref.fused_postscan_reorder(ids, g, keys, vals, m)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(permk), np.asarray(permr))
    if key_value:
        np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


@pytest.mark.parametrize("shift,bits", [(0, 8), (8, 8), (28, 4), (12, 6)])
@pytest.mark.parametrize("key_value", [False, True])
def test_radix_fused_postscan_reorder(shift, bits, key_value):
    """Fused radix postscan: in-kernel digits == host digits + fused oracle."""
    rng = np.random.RandomState(shift * 31 + bits)
    keys = jnp.asarray(rng.randint(0, 2**31 - 1, (3, 256)).astype(np.uint32))
    vals = jnp.asarray(rng.randint(0, 2**31 - 1, (3, 256), dtype=np.int32)) if key_value else None
    m = 1 << bits
    g = jnp.asarray(rng.randint(0, 10000, (3, m), dtype=np.int32))
    kk, vk, pk, permk = ops.radix_fused_postscan_reorder(keys, g, vals, shift, bits)
    kr, vr, pr, permr = ref.radix_fused_postscan_reorder(keys, g, vals, shift, bits)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(permk), np.asarray(permr))
    if key_value:
        np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


@pytest.mark.parametrize("L,T,m", SWEEP)
def test_device_histogram(L, T, m):
    ids = jnp.asarray(np.random.RandomState(7).randint(0, m, (L, T), dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.device_histogram(ids, m)),
        np.asarray(ref.device_histogram(ids, m)),
    )


@pytest.mark.parametrize("shift,bits", [(0, 8), (8, 8), (24, 8), (0, 4), (12, 6), (28, 4)])
def test_radix_kernels(shift, bits):
    rng = np.random.RandomState(shift + bits)
    keys = jnp.asarray(rng.randint(0, 2**31 - 1, (3, 512)).astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(ops.radix_tile_histograms(keys, shift, bits)),
        np.asarray(ref.radix_tile_histograms(keys, shift, bits)),
    )
    m = 1 << bits
    g = jnp.asarray(rng.randint(0, 10000, (3, m), dtype=np.int32))
    pos_k = ops.radix_tile_positions(keys, g, shift, bits)
    ids = ((np.asarray(keys) >> shift) & (m - 1)).astype(np.int32)
    pos_r = ref.tile_positions(jnp.asarray(ids), g, m)
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_r))


def test_even_bucket_ids_kernel():
    keys = jnp.asarray(np.random.RandomState(0).uniform(0, 1024, (2, 512)).astype(np.float32))
    ids = ops.even_bucket_ids(keys, 0.0, 1024.0, 64)
    expect = np.clip(np.floor(np.asarray(keys) / 16.0), 0, 63).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(ids), expect)


@settings(max_examples=15, deadline=None)
@given(
    t_exp=st.integers(7, 10),
    m=st.integers(2, 256),
    seed=st.integers(0, 100),
)
def test_property_kernels_match_oracle(t_exp, m, seed):
    """Histogram+positions kernels == oracle for arbitrary (T, m)."""
    t = 1 << t_exp
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, m, (2, t), dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.tile_histograms(ids, m)), np.asarray(ref.tile_histograms(ids, m))
    )
    g = jnp.asarray(rng.randint(0, 1000, (2, m), dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.tile_positions(ids, g, m)), np.asarray(ref.tile_positions(ids, g, m))
    )


@pytest.mark.parametrize("s,hd,causal,bq,bk", [
    (256, 64, True, 64, 64),
    (512, 128, True, 128, 64),
    (256, 64, False, 64, 128),
    (512, 32, True, 256, 256),
])
def test_flash_attention_kernel(s, hd, causal, bq, bk):
    rng = np.random.RandomState(s + hd)
    q = jnp.asarray(rng.randn(3, s, hd).astype(np.float32))
    k = jnp.asarray(rng.randn(3, s, hd).astype(np.float32))
    v = jnp.asarray(rng.randn(3, s, hd).astype(np.float32))
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 64)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, 256, 64)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, 256, 64)).astype(jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=5e-2
    )
