"""The one generator of inputs: it reads a traffic file of parameters and a
configuration, and makes every input on the device from the seed.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``keys``: the key distribution; ``"uniform"`` draws every bit at random
  (``jax.random.bits``), so the delta buckets are equally likely;
* ``values``: ``"arange"``, the input positions, so a reordered value says
  where its key came from;
* ``input_sets``: how many distinct key arrays the window cycles through,
  call ``i`` taking set ``i % input_sets``;
* ``checked_calls``: how many of the window's calls are kept, drawn from
  the seed, and compared with the reference after the window.

The loop is closed, with one client: a call is issued when the previous one
has finished.
"""

from __future__ import annotations

from typing import List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"uint32": jnp.uint32, "int32": jnp.int32}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits (seeds pass 2**31)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_inputs(traffic: Mapping, cfg: Mapping, seed: int,
                sharding) -> Tuple[List[jax.Array], jax.Array]:
    """``(key_sets, values)`` placed on ``sharding``, in one jitted call;
    ``values`` is ``None`` where the configuration has no ``value_dtype``."""
    if traffic["keys"] != "uniform":
        raise ValueError(f"unknown key distribution {traffic['keys']!r}")
    if traffic["values"] != "arange":
        raise ValueError(f"unknown values {traffic['values']!r}")
    n, sets = int(cfg["n"]), int(traffic["input_sets"])
    key_dtype = DTYPES[cfg["key_dtype"]]
    value_dtype = cfg.get("value_dtype")

    def draw(key):
        keys = [jax.random.bits(k, (n,), key_dtype)
                for k in jax.random.split(key, sets)]
        if value_dtype is None:                 # keys only
            return keys, None
        return keys, jnp.arange(n, dtype=DTYPES[value_dtype])

    out_shardings = ([sharding] * sets, None if value_dtype is None else sharding)
    return jax.jit(draw, out_shardings=out_shardings)(seed_key(seed))


def sample_rng(seed: int) -> np.random.Generator:
    """The host generator that picks which calls are checked."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
