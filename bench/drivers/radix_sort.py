"""``repro.ops.radix_sort`` of keys alone with default arguments, called
eagerly: LSD radix sort, 8-bit digits, four 256-bucket passes over 32-bit keys."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def build(cfg, mesh):
    from repro import ops

    return lambda keys, values: ops.radix_sort(keys)


def fetch(result) -> Dict[str, np.ndarray]:
    keys, values = result
    out = {"keys": np.asarray(keys)}
    if values is not None:            # a keys-only sort returns no values
        out["values"] = np.asarray(values)
    return out


def expected(cfg, keys: np.ndarray, values: np.ndarray) -> Dict[str, np.ndarray]:
    return reference.sorted_keys(keys)


def control(cfg, mesh):
    """The reference on the device with its ordering guarantee broken: a
    stable sort on the low 24 bits alone, as an LSD sort that took the keys
    for 24-bit ones and skipped its last 8-bit pass would leave them."""
    def skip_top_digit(keys, values):
        _, out = jax.lax.sort((keys & jnp.uint32(0xFFFFFF), keys), num_keys=1,
                              is_stable=True)
        return out, None

    return jax.jit(skip_top_digit)
