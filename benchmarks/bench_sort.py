"""Paper Tables 7/8 analogue: multisplit-based radix sort vs radix size r,
against the platform sort (jax.lax.sort standing in for CUB). Includes the
fused in-kernel digit path (plan layer, DESIGN.md §5) on a reduced shape —
the interpreter makes absolute pallas numbers meaningless on CPU, but the
row proves the zero-label pipeline end-to-end.

Set ``MS_BENCH_N`` (power-of-two exponent) to shrink for CI smoke runs."""

import os

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bench, row
from repro.core.sort import radix_sort, radix_sort_per_pass

N = 1 << int(os.environ.get("MS_BENCH_N", "18"))
N_PALLAS = min(N, 1 << 14)


def run_chained_vs_per_pass_radix():
    """DESIGN.md §10 measurement: the chained RadixPipeline (tiles resolved
    once, buffers padded once, ping-pong across digit passes) vs the PR-2
    per-pass execution (a full pad/tile/run/slice round trip per pass)."""
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 2**32, N, dtype=np.uint32))
    vals = jnp.arange(N, dtype=jnp.int32)
    results = {}
    for r in (4, 8):
        chained = jax.jit(lambda k, v, r=r: radix_sort(k, v, radix_bits=r)[0])
        per_pass = jax.jit(lambda k, v, r=r: radix_sort_per_pass(k, v, radix_bits=r)[0])
        t_c = bench(chained, keys, vals)
        t_p = bench(per_pass, keys, vals)
        tag = f"radix/r={r}"
        results[f"{tag}/chained_mpairs_s"] = round(N / t_c / 1e6, 2)
        results[f"{tag}/per_pass_mpairs_s"] = round(N / t_p / 1e6, 2)
        results[f"{tag}/speedup"] = round(t_p / t_c, 3)
        row(f"sort/kv/{tag}/chained-pipeline", t_c, f"{N / t_c / 1e6:.1f} Mpairs/s")
        row(f"sort/kv/{tag}/per-pass-legacy", t_p,
            f"{N / t_p / 1e6:.1f} Mpairs/s ({t_p / t_c:.2f}x slower)")
    return results


def main():
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 2**32, N, dtype=np.uint32))
    vals = jnp.arange(N, dtype=jnp.int32)

    for r in (4, 5, 6, 7, 8):
        f = jax.jit(lambda k, v, r=r: radix_sort(k, v, radix_bits=r)[0])
        t = bench(f, keys, vals)
        row(f"sort/kv/multisplit-sort/r={r}", t, f"{N / t / 1e6:.1f} Mpairs/s")

    t = bench(jax.jit(lambda k, v: jax.lax.sort((k, v), num_keys=1)[0]), keys, vals)
    row("sort/kv/platform-sort", t, f"{N / t / 1e6:.1f} Mpairs/s")

    for r in (6, 8):
        f = jax.jit(lambda k, r=r: radix_sort(k, radix_bits=r)[0])
        t = bench(f, keys)
        row(f"sort/keys/multisplit-sort/r={r}", t, f"{N / t / 1e6:.1f} Mkeys/s")
    t = bench(jax.jit(jax.lax.sort), keys)
    row("sort/keys/platform-sort", t, f"{N / t / 1e6:.1f} Mkeys/s")

    # Fused in-kernel digit path (no host label array): interpret-mode proof
    # run on a reduced shape; compiled TPU numbers are the deployment story.
    kp = keys[:N_PALLAS]
    f = jax.jit(lambda k: radix_sort(k, radix_bits=8, use_pallas=True, tile=1024)[0])
    t = bench(f, kp, warmup=1, trials=1)
    row("sort/keys/multisplit-sort/r=8/fused-pallas-interpret", t,
        f"{N_PALLAS / t / 1e6:.2f} Mkeys/s (interpret)")

    run_chained_vs_per_pass_radix()


if __name__ == "__main__":
    main()
