"""Paper Table 11 analogue: device-wide histogram (Even + Range scenarios)
vs the platform baseline (jnp.histogram — XLA's native path).

The "ours" rows run the ``counts_only`` partial pipeline (DESIGN.md §10):
prescan + tree-reduce, tiles from the shared heuristic cache — no scan, no
scatter.
"""

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bench, row
from repro.core.histogram import histogram_even, histogram_range

N = 1 << 20
M_SWEEP = (2, 8, 32, 64, 256)
RANGE_M_SWEEP = (8, 64, 256)


def main():
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.uniform(0, 1024.0, N).astype(np.float32))
    results = {}

    for m in M_SWEEP:
        f = jax.jit(lambda k, m=m: histogram_even(k, 0.0, 1024.0, m))
        t = bench(f, keys)
        row(f"histogram/even/m={m}/ours", t, f"{N / t / 1e6:.1f} Melem/s")
        g = jax.jit(lambda k, m=m: jnp.histogram(k, bins=m, range=(0.0, 1024.0))[0])
        t_p = bench(g, keys)
        row(f"histogram/even/m={m}/platform", t_p, f"{N / t_p / 1e6:.1f} Melem/s")
        results[f"even/m={m}/counts_only_melem_s"] = round(N / t / 1e6, 2)
        results[f"even/m={m}/platform_melem_s"] = round(N / t_p / 1e6, 2)
        results[f"even/m={m}/vs_platform"] = round(t_p / t, 3)

    for m in RANGE_M_SWEEP:
        splitters = jnp.asarray(np.sort(rng.uniform(0, 1024.0, m - 1)).astype(np.float32))
        f = jax.jit(lambda k, s=splitters: histogram_range(k, s))
        t = bench(f, keys)
        row(f"histogram/range/m={m}/ours", t, f"{N / t / 1e6:.1f} Melem/s")
        g = jax.jit(lambda k, s=splitters: jnp.histogram(
            k, bins=jnp.concatenate([jnp.asarray([-1e30]), s, jnp.asarray([1e30])]))[0])
        t = bench(g, keys)
        row(f"histogram/range/m={m}/platform", t, f"{N / t / 1e6:.1f} Melem/s")

    return results


if __name__ == "__main__":
    main()
