"""Multisplit roofline tracker (ISSUE 6): ideal-bytes model vs measured
bandwidth for the three radix-sort execution modes.

    PYTHONPATH=src:. python benchmarks/roofline_multisplit.py [--quick]
        [--ci-floor 1.15]

The paper's multisplit is bandwidth-bound: every {prescan, scan, postscan,
scatter} sweep must at minimum read the keys twice (prescan + postscan),
write them once, round-trip the values when key-value, and round-trip the
L×m tile-histogram matrix. The tracker:

1. probes the machine's PEAK sustainable bandwidth with a large device
   copy (the same probe a GPU roofline would run with a device memcpy);
2. computes the IDEAL bytes of each execution mode from the schedule —
   per-pass and chained move the same ideal bytes over ⌈key_bits/r⌉
   sweeps (chained only removes pad/slice overhead, which is exactly why
   it sits closer to the roofline), the FUSED mode halves the sweep count
   (digit pairs, DESIGN.md §13) at the cost of an L×m² histogram matrix;
3. measures each mode and reports time, effective throughput, and the
   FRACTION OF ROOFLINE = (ideal_bytes / peak_bw) / measured_time.

``--ci-floor X`` exits non-zero when fused throughput < X× chained at the
headline r=8 point — the CI perf-smoke guard (S5). ``--quick`` shrinks n.
"""

import argparse
import math
import sys

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bench, row
from repro.core.pipeline import RadixPipeline, radix_pass_pairs, radix_passes
from repro.core.sort import radix_sort, radix_sort_per_pass

KEY_BYTES = 4
KEY_BITS = 32


def probe_peak_bandwidth(nbytes: int = 1 << 26, trials: int = 5) -> float:
    """Peak sustainable device bandwidth (bytes/s) via a large copy: one
    read + one write of ``nbytes``."""
    x = jnp.arange(nbytes // 4, dtype=jnp.uint32)
    copy = jax.jit(lambda a: a + jnp.uint32(1))   # forces a real materialize
    t = bench(copy, x, trials=trials)
    return 2 * nbytes / t


def ideal_sweep_bytes(n: int, m_scan: int, tiles: int, key_value: bool) -> int:
    """Minimum HBM traffic of ONE {prescan, scan, postscan, scatter} sweep:
    keys are read by the prescan and the postscan and written once by the
    scatter; values round-trip once; the L×m histogram matrix is written by
    the prescan and read (post-scan) by the postscan."""
    keys_bytes = 3 * KEY_BYTES * n
    vals_bytes = 2 * KEY_BYTES * n if key_value else 0
    hist_bytes = 2 * KEY_BYTES * tiles * m_scan
    return keys_bytes + vals_bytes + hist_bytes


def ideal_sort_bytes(n: int, radix_bits: int, tile: int, key_value: bool,
                     fused: bool, segments: int = 1) -> int:
    """Ideal bytes of the whole sort under the given schedule."""
    tiles = math.ceil(n / tile)
    total = 0
    if fused:
        schedule = [(s, b) for s, b, _ in radix_pass_pairs(radix_bits, KEY_BITS)]
    else:
        schedule = radix_passes(radix_bits, KEY_BITS)
    for _, bits in schedule:
        total += ideal_sweep_bytes(n, (1 << bits) * segments, tiles, key_value)
    return total


def run(n: int, radix_bits: int, key_value: bool, peak_bw: float,
        trials: int = 3, emit_rows: bool = True) -> dict:
    """Measure per-pass / chained / fused at one (n, r) point and return the
    flat result dict (throughput + fraction-of-roofline per mode)."""
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 2**32, n, dtype=np.uint32))
    vals = jnp.arange(n, dtype=jnp.int32) if key_value else None

    pipe_c = RadixPipeline(n, radix_bits=radix_bits, backend="vmap",
                           key_value=key_value)
    pipe_f = RadixPipeline(n, radix_bits=radix_bits, backend="vmap",
                           key_value=key_value, fuse_digits=True)

    def timed(fn):
        if key_value:
            f = jax.jit(lambda k, v: fn(k, v)[0])
            return bench(f, keys, vals, trials=trials)
        f = jax.jit(lambda k: fn(k, None)[0])
        return bench(f, keys, trials=trials)

    t_p = timed(lambda k, v: radix_sort_per_pass(
        k, v, radix_bits=radix_bits, backend="vmap"))
    t_c = timed(lambda k, v: radix_sort(
        k, v, radix_bits=radix_bits, backend="vmap"))
    t_f = timed(lambda k, v: radix_sort(
        k, v, radix_bits=radix_bits, backend="vmap", fuse_digits=True))

    ideal_u = ideal_sort_bytes(n, radix_bits, pipe_c.tile, key_value, False)
    ideal_f = ideal_sort_bytes(n, radix_bits, pipe_f.tile, key_value, True)

    out = {}
    tag = f"roofline/r={radix_bits}"
    for mode, t, ideal in (("per_pass", t_p, ideal_u),
                           ("chained", t_c, ideal_u),
                           ("fused", t_f, ideal_f)):
        frac = (ideal / peak_bw) / t
        out[f"{tag}/{mode}_mkeys_s"] = round(n / t / 1e6, 2)
        out[f"{tag}/{mode}_roofline_frac"] = round(frac, 4)
        if emit_rows:
            row(f"sort/{'kv' if key_value else 'keys'}/{tag}/{mode}", t,
                f"{n / t / 1e6:.1f} Mkeys/s, {100 * frac:.2f}% of roofline")
    out[f"{tag}/fused_vs_chained_speedup"] = round(t_c / t_f, 3)
    out[f"{tag}/fused_sweeps"] = pipe_f.n_sweeps
    out[f"{tag}/chained_sweeps"] = pipe_c.n_sweeps
    if emit_rows:
        row(f"sort/{'kv' if key_value else 'keys'}/{tag}/fused_vs_chained",
            t_f, f"{t_c / t_f:.3f}x chained")
    return out


def main(quick: bool = False, ci_floor: float = None) -> int:
    n = 1 << (16 if quick else 18)
    trials = 2 if quick else 3
    peak_bw = probe_peak_bandwidth()
    print(f"# peak bandwidth probe: {peak_bw / 1e9:.2f} GB/s "
          f"(host={jax.default_backend()})")

    results = {"peak_bw_gb_s": round(peak_bw / 1e9, 2)}
    for bits in ((8,) if quick else (8, 7, 5)):
        results.update(run(n, bits, key_value=not quick, peak_bw=peak_bw,
                           trials=trials))

    headline = results["roofline/r=8/fused_vs_chained_speedup"]
    if ci_floor is not None and headline < ci_floor:
        print(f"# FAIL: fused radix at r=8 is {headline:.3f}x chained, "
              f"below the {ci_floor:.2f}x CI floor", file=sys.stderr)
        return 1
    if ci_floor is not None:
        print(f"# ok: fused radix at r=8 is {headline:.3f}x chained "
              f"(floor {ci_floor:.2f}x)")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small-n smoke")
    ap.add_argument("--ci-floor", type=float, default=None,
                    help="exit 1 if fused < FLOOR x chained at r=8")
    a = ap.parse_args()
    sys.exit(main(quick=a.quick, ci_floor=a.ci_floor))
