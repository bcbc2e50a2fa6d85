"""Heuristic drift gate (ISSUE 7, DESIGN.md §14): heuristic vs autotuned.

    PYTHONPATH=src:. python benchmarks/autotune_drift.py [--quick]
        [--ci-max 1.25]

PR 6 found two hand-tuned flip points measurably stale; the self-tuning
layer exists so that can't silently happen again. This tracker closes the
loop on the HEURISTICS themselves: for a small (n, m) grid it resolves each
shape twice — once through the untouched heuristics, once through the
memory-only joint autotune search (the heuristic's own choice is always in
the searched grid, so the tuned plan can only tie or win modulo noise) —
and reports the gap ``t_heuristic / t_tuned``.

A gap of 1.0 means the heuristic still picks what measurement picks; the
gap grows as the cost model rots. ``--ci-max X`` exits non-zero when any
grid point's gap exceeds ``X`` — the CI drift gate.
"""

import argparse
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.core.identifiers import EvenSpec
from repro.core.pipeline import clear_tile_cache, family_decision, make_plan, set_autotune
from repro.core.pipeline import autotune as _at


def run_drift(n: int, m: int, *, method: str = "bms", backend: str = "vmap",
              candidates=(256, 512, 1024, 2048, 4096), trials: int = 3,
              emit_rows: bool = True) -> dict:
    """Gap of one shape class: heuristic-resolved plan vs the joint-search
    winner (tile x family), timed on the same synthetic keys."""
    spec = EvenSpec(0.0, float(1 << 30), m)
    keys = jnp.asarray(
        np.random.RandomState(0).randint(0, 1 << 30, n, dtype=np.uint32)
    )

    prev = _at._CONFIG
    try:
        # 1) resolve through the untouched heuristics
        set_autotune(False, persist=False)
        clear_tile_cache()
        p_h = make_plan(n, m, method=method, backend=backend, bucket_fn=spec)
        fam_h = family_decision(n, m, method, backend)[0]

        # 2) resolve through the measured search, with the heuristic's own
        #    pick in the grid
        grid = tuple(sorted(set(candidates) | {p_h.tile}))
        set_autotune(True, persist=False, trials=trials, candidates=grid)
        clear_tile_cache()
        p_t = make_plan(n, m, method=method, backend=backend, bucket_fn=spec)
        fam_t = family_decision(n, m, method, backend)[0]
    finally:
        _at._CONFIG = prev
        clear_tile_cache()

    # time both AFTER all searching, interleaved: neither side gets the
    # warmed-caches advantage of going second
    run_h = jax.jit(lambda k: p_h(k).keys)
    run_t = jax.jit(lambda k: p_t(k).keys)
    jax.block_until_ready(run_h(keys))
    jax.block_until_ready(run_t(keys))
    ts_h, ts_t = [], []
    for _ in range(max(trials, 3)):
        t0 = time.perf_counter()
        jax.block_until_ready(run_h(keys))
        ts_h.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(run_t(keys))
        ts_t.append(time.perf_counter() - t0)
    t_h, t_t = float(np.median(ts_h)), float(np.median(ts_t))

    gap = t_h / t_t
    tag = f"autotune_drift/n=2^{n.bit_length() - 1}/m={m}"
    out = {
        f"{tag}/heuristic_us": round(t_h * 1e6, 1),
        f"{tag}/tuned_us": round(t_t * 1e6, 1),
        f"{tag}/gap": round(gap, 3),
        f"{tag}/heuristic_plan": f"tile={p_h.tile},family={fam_h}",
        f"{tag}/tuned_plan": f"tile={p_t.tile},family={fam_t}",
    }
    if emit_rows:
        row(f"{tag}/heuristic", t_h,
            f"tile={p_h.tile} family={fam_h}")
        row(f"{tag}/tuned", t_t,
            f"tile={p_t.tile} family={fam_t} gap={gap:.3f}x")
    return out


def main(quick: bool = False, ci_max: float = None) -> int:
    # quick keeps n at 2^16 on purpose: the heuristic flip points were
    # benched there (PR 6), and tiny n makes the gap mostly launch noise
    n = 1 << 16
    trials = 2 if quick else 3
    candidates = (256, 1024) if quick else (256, 512, 1024, 2048, 4096)

    results = {}
    gaps = {}
    for m in (8, 256):
        out = run_drift(n, m, candidates=candidates, trials=trials)
        results.update(out)
        tag = f"autotune_drift/n=2^{n.bit_length() - 1}/m={m}"
        gaps[tag] = out[f"{tag}/gap"]

    worst_tag = max(gaps, key=gaps.get)
    worst = gaps[worst_tag]
    if ci_max is not None and worst > ci_max:
        print(f"# FAIL: heuristic is {worst:.3f}x slower than autotuned at "
              f"{worst_tag} — above the {ci_max:.2f}x drift gate; re-derive "
              f"the heuristic (see tiles.py) or re-bench its flip points",
              file=sys.stderr)
        return 1
    if ci_max is not None:
        print(f"# ok: worst heuristic-vs-tuned gap {worst:.3f}x at "
              f"{worst_tag} (gate {ci_max:.2f}x)")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small-n smoke")
    ap.add_argument("--ci-max", type=float, default=None,
                    help="exit 1 if heuristic > MAX x slower than autotuned")
    a = ap.parse_args()
    sys.exit(main(quick=a.quick, ci_max=a.ci_max))
