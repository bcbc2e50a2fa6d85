"""``repro.ops.multisplit_key_value`` with default arguments, called eagerly
as a user's program calls it: no backend, tile, method or family."""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

FIELDS = ("keys", "values", "bucket_counts", "bucket_starts", "permutation")


def build(cfg, mesh):
    from repro import ops

    spec = ops.delta_buckets(int(cfg["num_buckets"]), key_max=int(cfg["key_max"]))
    return lambda keys, values: ops.multisplit_key_value(keys, values, spec)


def fetch(result) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(result, f)) for f in FIELDS
            if getattr(result, f, None) is not None}


def expected(cfg, keys: np.ndarray, values: np.ndarray) -> Dict[str, np.ndarray]:
    m = int(cfg["num_buckets"])
    ids = reference.delta_bucket_ids(keys, m, int(cfg["key_max"]))
    return reference.stable_multisplit(keys, values, ids, m)


class ControlResult(NamedTuple):
    keys: jax.Array
    values: jax.Array
    bucket_counts: jax.Array
    bucket_starts: jax.Array
    permutation: jax.Array


def unstable_partition(keys, values, num_buckets: int, key_max: int) -> ControlResult:
    """The reference on the device with its stability guarantee broken: keys
    of one bucket come out in reverse input order. Every other field of the
    result (bucket counts and starts) stays exact."""
    n = keys.shape[0]
    delta = max(1, key_max // num_buckets)
    ids = jnp.minimum(keys // jnp.uint32(delta), num_buckets - 1).astype(jnp.int32)
    rev = jnp.arange(n - 1, -1, -1, dtype=jnp.int32)
    _, rev_sorted = jax.lax.sort((ids, rev), num_keys=2)
    order = (n - 1) - rev_sorted
    counts = jnp.zeros((num_buckets,), jnp.int32).at[ids].add(1)
    permutation = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    return ControlResult(keys[order], values[order], counts,
                         jnp.cumsum(counts) - counts, permutation)


def control(cfg, mesh):
    m, key_max = int(cfg["num_buckets"]), int(cfg["key_max"])
    return jax.jit(lambda keys, values: unstable_partition(keys, values, m, key_max))
