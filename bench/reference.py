"""Plain numpy references and the bytes a call must move.

Nothing here imports the program or takes anything it made: the references
see only host copies of the inputs, so they judge the program's answers and
not its intermediate state.
"""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np


def delta_bucket_ids(keys: np.ndarray, num_buckets: int, key_max: int) -> np.ndarray:
    """Equal-width buckets over ``[0, key_max)``: ``min(key // delta, m - 1)``
    with ``delta = max(1, key_max // m)`` (paper §6)."""
    delta = np.uint64(max(1, key_max // num_buckets))
    ids = np.minimum(keys.astype(np.uint64) // delta, num_buckets - 1)
    return ids.astype(np.uint16 if num_buckets <= 1 << 16 else np.uint32)


def stable_multisplit(keys: np.ndarray, values: np.ndarray, ids: np.ndarray,
                      num_buckets: int) -> Dict[str, np.ndarray]:
    """A stable partition by bucket id: within a bucket, keys keep their input
    order. ``permutation[i]`` is the output position of input ``i``."""
    order = np.argsort(ids, kind="stable")
    counts = np.bincount(ids, minlength=num_buckets).astype(np.int32)
    permutation = np.empty(keys.shape[0], np.int32)
    permutation[order] = np.arange(keys.shape[0], dtype=np.int32)
    return {
        "keys": keys[order],
        "values": values[order],
        "bucket_counts": counts,
        "bucket_starts": (np.cumsum(counts) - counts).astype(np.int32),
        "permutation": permutation,
    }


def sorted_keys(keys: np.ndarray) -> Dict[str, np.ndarray]:
    return {"keys": np.sort(keys, kind="stable")}


def mismatches(got: Mapping[str, np.ndarray],
               want: Mapping[str, np.ndarray]) -> Dict[str, int]:
    """``<field>_mismatch``: how many elements of each expected field differ
    from the program's; a field that is missing or has another shape counts
    every element."""
    out = {}
    for name, w in want.items():
        g = got.get(name)
        if g is None or np.shape(g) != np.shape(w):
            out[f"{name}_mismatch"] = int(np.size(w))
        else:
            out[f"{name}_mismatch"] = int(np.count_nonzero(np.asarray(g) != w))
    return out


def necessary_bytes(n: int, key_bytes: int, value_bytes: int) -> int:
    """Bytes one call must move through HBM: every key (and value) read once
    and written once, ``n * (key_bytes + value_bytes) * 2``.

    It depends on the problem alone. It ignores the implementation's tiles,
    its sweep count and its scratch arrays on purpose: those are what a
    faster program removes, so counting them would let a change to the tile
    or the sweep schedule move the yardstick, and a share of the roofline
    built on this number cannot pass 100%."""
    return int(n) * (int(key_bytes) + int(value_bytes)) * 2
