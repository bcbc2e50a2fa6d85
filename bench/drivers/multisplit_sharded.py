"""``jax.jit(repro.core.distributed.make_multisplit_sharded(spec, mesh, "x",
key_value=True))`` with default arguments over a one-axis mesh of every
chip: a global stable key-value multisplit of keys and values sharded on
``"x"``, each chip's shard exchanged with every other by all-to-all."""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.drivers import multisplit_key_value as one_chip

FIELDS = ("keys", "values", "bucket_counts", "bucket_starts")


def build(cfg, mesh):
    from repro import ops
    from repro.core.distributed import make_multisplit_sharded

    spec = ops.delta_buckets(int(cfg["num_buckets"]), key_max=int(cfg["key_max"]))
    return jax.jit(make_multisplit_sharded(spec, mesh, "x", key_value=True))


def fetch(result) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(result, f)) for f in FIELDS
            if getattr(result, f, None) is not None}


def expected(cfg, keys: np.ndarray, values: np.ndarray) -> Dict[str, np.ndarray]:
    want = one_chip.expected(cfg, keys, values)
    return {f: want[f] for f in FIELDS}


def misplaced_shards(result, mesh) -> int:
    """Shards of the keys and values that are not where the mesh puts them:
    chip ``d`` of the mesh holds global positions ``[d*n/D, (d+1)*n/D)``."""
    devices = list(mesh.devices.flat)
    bad = 0
    for arr in (result.keys, result.values):
        if arr is None:
            bad += len(devices)
            continue
        per = arr.shape[0] // len(devices)
        shards = {s.device: s for s in arr.addressable_shards}
        for d, dev in enumerate(devices):
            s = shards.get(dev)
            ok = (s is not None and s.data.shape == (per,)
                  and (s.index[0].start or 0) == d * per)
            bad += not ok
    return bad


def extra_checks(result, mesh) -> Dict[str, int]:
    return {"shards_misplaced": misplaced_shards(result, mesh)}


def control(cfg, mesh):
    m, key_max = int(cfg["num_buckets"]), int(cfg["key_max"])
    sharded = NamedSharding(mesh, P("x"))
    replicated = NamedSharding(mesh, P())
    return jax.jit(
        lambda keys, values: one_chip.unstable_partition(keys, values, m, key_max),
        out_shardings=one_chip.ControlResult(sharded, sharded, replicated,
                                             replicated, sharded))
