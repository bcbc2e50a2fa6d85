"""Paper Tables 3/4/5 analogue: multisplit throughput vs bucket count, for
DMS / WMS / BMS vs the sort-based baselines (RB-sort, direct key sort), for
key-only and key-value, plus Table 6's input-distribution sensitivity, plus
the fused-plan vs legacy-unfused pipeline comparison (DESIGN.md §6).

Rates are Mkeys/s on THIS host (CPU — relative standings are the
reproduction target; absolute GPU numbers are in the paper).

Set ``MS_BENCH_N`` (power-of-two exponent, e.g. 14) to shrink the problem
for CI smoke runs."""

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bench, row
from repro.core.identifiers import delta_buckets
from repro.core.multisplit import (
    batched_multisplit,
    multisplit,
    multisplit_unfused,
    segmented_multisplit,
)
from repro.core.sort import direct_sort_multisplit, rb_sort_multisplit

N = 1 << int(os.environ.get("MS_BENCH_N", "18"))
M_SWEEP = (2, 8, 32, 128, 256)


def _keys(n=N, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 2**30, n, dtype=np.uint32))


def _binomial_keys(m, n=N, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.binomial(m - 1, 0.5, size=n).astype(np.uint32)
    width = 2**30 // m
    return jnp.asarray(ids * width + rng.randint(0, width, n).astype(np.uint32))


def run(key_value=True):
    keys = _keys()
    vals = jnp.arange(N, dtype=jnp.int32)
    kv = "kv" if key_value else "keys"

    for m in M_SWEEP:
        bf = delta_buckets(m, 2**30)
        for method in ("dms", "wms", "bms"):
            f = jax.jit(functools.partial(
                multisplit, bucket_fn=bf, method=method))
            args = (keys, vals) if key_value else (keys,)
            fn = (lambda k, v: f(k, values=v)) if key_value else (lambda k: f(k))
            t = bench(jax.jit(fn), *args)
            row(f"multisplit/{kv}/m={m}/{method}", t, f"{N / t / 1e6:.1f} Mkeys/s")
        # RB-sort baseline (paper §3.4)
        if key_value:
            rb = jax.jit(lambda k, v: rb_sort_multisplit(k, bf, v).keys)
            t = bench(rb, keys, vals)
        else:
            rb = jax.jit(lambda k: rb_sort_multisplit(k, bf).keys)
            t = bench(rb, keys)
        row(f"multisplit/{kv}/m={m}/rb-sort", t, f"{N / t / 1e6:.1f} Mkeys/s")

    # direct full sort (paper §3.3 / Table 3 reference)
    if key_value:
        t = bench(jax.jit(lambda k, v: direct_sort_multisplit(k, v)[0]), keys, vals)
    else:
        t = bench(jax.jit(lambda k: direct_sort_multisplit(k)[0]), keys)
    row(f"multisplit/{kv}/full-radix-sort-baseline", t, f"{N / t / 1e6:.1f} Mkeys/s")


def run_distributions():
    """Table 6 analogue: uniform vs binomial key distribution, m=256."""
    m = 256
    bf = delta_buckets(m, 2**30)
    f = jax.jit(lambda k: multisplit(k, bf, method="bms").keys)
    for name, keys in (("uniform", _keys()), ("binomial", _binomial_keys(m))):
        t = bench(f, keys)
        row(f"multisplit/dist={name}/m=256/bms", t, f"{N / t / 1e6:.1f} Mkeys/s")


def run_fused_vs_legacy():
    """The tentpole measurement: the plan's fused single-pass postscan vs the
    legacy three-pass (positions, key reorder, value reorder) orchestration."""
    results = {}
    keys = _keys()
    vals = jnp.arange(N, dtype=jnp.int32)
    for m in (32, 256):
        bf = delta_buckets(m, 2**30)
        for method in ("wms", "bms"):
            fused = jax.jit(lambda k, v, bf=bf, me=method: multisplit(
                k, bf, values=v, method=me).keys)
            legacy = jax.jit(lambda k, v, bf=bf, me=method: multisplit_unfused(
                k, bf, values=v, method=me).keys)
            t_f = bench(fused, keys, vals)
            t_l = bench(legacy, keys, vals)
            tag = f"m={m}/{method}"
            results[f"{tag}/fused_mkeys_s"] = round(N / t_f / 1e6, 2)
            results[f"{tag}/legacy_mkeys_s"] = round(N / t_l / 1e6, 2)
            results[f"{tag}/speedup"] = round(t_l / t_f, 3)
            row(f"multisplit/kv/{tag}/fused-plan", t_f, f"{N / t_f / 1e6:.1f} Mkeys/s")
            row(f"multisplit/kv/{tag}/legacy-unfused", t_l,
                f"{N / t_l / 1e6:.1f} Mkeys/s ({t_l / t_f:.2f}x slower)")
    return results


def run_batched_vs_host_loop():
    """DESIGN.md §9 measurement: b independent multisplits as ONE batched
    (and one segmented) plan launch vs the host loop every consumer used to
    write (one flat plan call per row). The acceptance bar is batched >=
    1.5x host-loop on the vmap backend at b=64, n=4096, m=32."""
    b = int(os.environ.get("MS_BENCH_B", "64"))
    n = 1 << int(os.environ.get("MS_BENCH_BN", "12"))        # 4096 per row
    m = 32
    bf = delta_buckets(m, 2**30)
    rng = np.random.RandomState(0)
    keys2d = jnp.asarray(rng.randint(0, 2**30, (b, n), dtype=np.uint32))
    vals2d = jnp.asarray(rng.randint(0, 2**20, (b, n), dtype=np.int32))
    starts = jnp.arange(b, dtype=jnp.int32) * n              # equal segments

    results = {}
    total = b * n

    batched = jax.jit(lambda k, v: batched_multisplit(k, bf, v, method="bms").keys)
    t_b = bench(batched, keys2d, vals2d)

    seg = jax.jit(
        lambda k, v: segmented_multisplit(k, bf, starts, v, method="bms").keys
    )
    t_s = bench(seg, keys2d.reshape(-1), vals2d.reshape(-1))

    # host-loop baseline: what consumers did before plans had a batch axis —
    # one flat multisplit call per row, op-by-op dispatch (consumers call the
    # module-level multisplit eagerly: data pipeline, host-side routing).
    def host_loop(k2, v2):
        return [multisplit(k2[i], bf, v2[i], method="bms").keys for i in range(b)]

    t_h = bench(host_loop, keys2d, vals2d)

    # second reference point: the loop with the per-row call jitted — only
    # the b-per-step dispatch overhead remains.
    row_f = jax.jit(lambda k, v: multisplit(k, bf, v, method="bms").keys)

    def host_loop_jit(k2, v2):
        return [row_f(k2[i], v2[i]) for i in range(b)]

    t_hj = bench(host_loop_jit, keys2d, vals2d)

    tag = f"b={b}/n={n}/m={m}"
    results[f"{tag}/batched_mkeys_s"] = round(total / t_b / 1e6, 2)
    results[f"{tag}/segmented_mkeys_s"] = round(total / t_s / 1e6, 2)
    results[f"{tag}/host_loop_mkeys_s"] = round(total / t_h / 1e6, 2)
    results[f"{tag}/host_loop_jit_mkeys_s"] = round(total / t_hj / 1e6, 2)
    results[f"{tag}/batched_speedup"] = round(t_h / t_b, 3)
    results[f"{tag}/segmented_speedup"] = round(t_h / t_s, 3)
    results[f"{tag}/batched_speedup_vs_jit_loop"] = round(t_hj / t_b, 3)
    row(f"multisplit/kv/{tag}/batched-plan", t_b, f"{total / t_b / 1e6:.1f} Mkeys/s")
    row(f"multisplit/kv/{tag}/segmented-plan", t_s, f"{total / t_s / 1e6:.1f} Mkeys/s")
    row(f"multisplit/kv/{tag}/host-loop", t_h,
        f"{total / t_h / 1e6:.1f} Mkeys/s ({t_h / t_b:.2f}x slower than batched)")
    row(f"multisplit/kv/{tag}/host-loop-jit", t_hj,
        f"{total / t_hj / 1e6:.1f} Mkeys/s ({t_hj / t_b:.2f}x slower than batched)")
    return results


def run_fused_labels_vs_materialized():
    """ISSUE 4 measurement: in-tile fused labels (hashable specs evaluated
    inside the tile stage / kernels) vs the pre-PR-4 materialized-labels
    execution, which the CallableSpec escape hatch still exercises — the
    full n-sized int32 label array is computed, padded and carried through
    the pipeline.  Flat multisplit at m∈{32,256} plus the chained radix
    sort (BitfieldSpec digits, radix_bits∈{5,8} → m∈{32,256} per pass)."""
    from repro import ops
    from repro.core.pipeline import radix_passes

    results = {}
    keys = _keys()
    vals = jnp.arange(N, dtype=jnp.int32)

    for m in (32, 256):
        spec = ops.delta_buckets(m, 2**30)
        # identical math, forced through the materialized-labels path
        opaque = ops.from_fn(spec.emit, m, name=f"opaque-delta{m}")
        fused = jax.jit(lambda k, v, s=spec: ops.multisplit(k, s, v).keys)
        mater = jax.jit(lambda k, v, s=opaque: ops.multisplit(k, s, v).keys)
        t_f = bench(fused, keys, vals)
        t_m = bench(mater, keys, vals)
        tag = f"fused_labels/flat/m={m}"
        results[f"{tag}/fused_mkeys_s"] = round(N / t_f / 1e6, 2)
        results[f"{tag}/materialized_mkeys_s"] = round(N / t_m / 1e6, 2)
        results[f"{tag}/speedup"] = round(t_m / t_f, 3)
        row(f"multisplit/kv/{tag}/fused", t_f, f"{N / t_f / 1e6:.1f} Mkeys/s")
        row(f"multisplit/kv/{tag}/materialized", t_m,
            f"{N / t_m / 1e6:.1f} Mkeys/s ({t_m / t_f:.2f}x slower)")

    for bits, m in ((5, 32), (8, 256)):
        fused_sort = jax.jit(
            lambda k, v, b=bits: ops.radix_sort(k, v, radix_bits=b)[0]
        )

        def materialized_sort(k, v, b=bits):
            # per-pass digit as an opaque callable: labels materialize
            from repro.core.multisplit import multisplit as core_multisplit

            for shift, width in radix_passes(b, 32):
                digit = ops.from_fn(
                    ops.BitfieldSpec(shift, width).emit, 1 << width,
                    name=f"opaque-radix{shift}",
                )
                res = core_multisplit(k, digit, v)
                k, v = res.keys, res.values
            return k

        mater_sort = jax.jit(materialized_sort)
        t_f = bench(fused_sort, keys, vals)
        t_m = bench(mater_sort, keys, vals)
        tag = f"fused_labels/radix/m={m}"
        results[f"{tag}/fused_mkeys_s"] = round(N / t_f / 1e6, 2)
        results[f"{tag}/materialized_mkeys_s"] = round(N / t_m / 1e6, 2)
        results[f"{tag}/speedup"] = round(t_m / t_f, 3)
        row(f"sort/kv/{tag}/fused", t_f, f"{N / t_f / 1e6:.1f} Mkeys/s")
        row(f"sort/kv/{tag}/materialized", t_m,
            f"{N / t_m / 1e6:.1f} Mkeys/s ({t_m / t_f:.2f}x slower)")

    return results


def run_packed_vs_onehot(quick: bool = False):
    """ISSUE 5 measurement: the packed-counter kernel family (bit-packed
    subword counters + two-level rank, DESIGN.md §12) vs the dense one-hot
    family, on the SAME plans — only ``family`` differs, outputs are bitwise
    identical.  Flat key-value multisplit sweeping m ∈ {8, 32, 64, 128, 256}
    plus the chained radix sort at radix_bits ∈ {5, 8}; ``quick=True``
    restricts to the m=256 flat + radix points (the CI perf-smoke floor)."""
    from repro.core.sort import radix_sort

    results = {}
    keys = _keys()
    vals = jnp.arange(N, dtype=jnp.int32)

    m_sweep = (256,) if quick else (8, 32, 64, 128, 256)
    for m in m_sweep:
        bf = delta_buckets(m, 2**30)
        timed = {}
        for family in ("packed", "onehot"):
            f = jax.jit(lambda k, v, bf=bf, fam=family: multisplit(
                k, bf, values=v, method="bms", family=fam).keys)
            timed[family] = bench(f, keys, vals)
        tag = f"packed_vs_onehot/flat/m={m}"
        results[f"{tag}/packed_mkeys_s"] = round(N / timed["packed"] / 1e6, 2)
        results[f"{tag}/onehot_mkeys_s"] = round(N / timed["onehot"] / 1e6, 2)
        results[f"{tag}/speedup"] = round(timed["onehot"] / timed["packed"], 3)
        row(f"multisplit/kv/{tag}/packed", timed["packed"],
            f"{N / timed['packed'] / 1e6:.1f} Mkeys/s")
        row(f"multisplit/kv/{tag}/onehot", timed["onehot"],
            f"{N / timed['onehot'] / 1e6:.1f} Mkeys/s "
            f"({timed['onehot'] / timed['packed']:.2f}x slower)")

    bit_sweep = ((8, 256),) if quick else ((5, 32), (8, 256))
    for bits, m in bit_sweep:
        timed = {}
        for family in ("packed", "onehot"):
            f = jax.jit(lambda k, v, b=bits, fam=family: radix_sort(
                k, v, radix_bits=b, family=fam)[0])
            timed[family] = bench(f, keys, vals)
        tag = f"packed_vs_onehot/radix/m={m}"
        results[f"{tag}/packed_mkeys_s"] = round(N / timed["packed"] / 1e6, 2)
        results[f"{tag}/onehot_mkeys_s"] = round(N / timed["onehot"] / 1e6, 2)
        results[f"{tag}/speedup"] = round(timed["onehot"] / timed["packed"], 3)
        row(f"sort/kv/{tag}/packed", timed["packed"],
            f"{N / timed['packed'] / 1e6:.1f} Mkeys/s")
        row(f"sort/kv/{tag}/onehot", timed["onehot"],
            f"{N / timed['onehot'] / 1e6:.1f} Mkeys/s "
            f"({timed['onehot'] / timed['packed']:.2f}x slower)")

    return results


def run_oblivious_vs_gather(quick: bool = False):
    """ISSUE 8 measurement (DESIGN.md §15): the gather-free OBLIVIOUS kernel
    bodies (one-hot selects, 16-bit rank planes, permutation matmuls — the
    only forms Mosaic lowers with ``interpret=False``) vs the legacy gather
    forms, through the SAME pallas entry points in interpret mode, outputs
    bitwise identical.  Points: the packed positions/fused kernels at m=256
    and the fused2 pair kernels at 2r=8, plus the RangeSpec balanced-tree
    emit vs the serialized compare chain at s ∈ {31, 255} (satellite 1).
    ``speedup = t_gather / t_oblivious``; the CI floor asserts the oblivious
    forms cost <= ~1.1x the gather forms even on a host, where gathers are
    native."""
    from repro.core.identifiers import BitfieldSpec, RangeSpec
    from repro.kernels import ops as kops

    results = {}
    t = 1024                                   # the oblivious packed tile cap
    n_tiles = max(N // t, 1)
    rng = np.random.RandomState(0)
    m = 256
    ids = jnp.asarray(rng.randint(0, m, (n_tiles, t), dtype=np.int32))
    keys = jnp.asarray(rng.randint(0, 2**30, (n_tiles, t)).astype(np.uint32))
    vals = jnp.arange(n_tiles * t, dtype=jnp.int32).reshape(n_tiles, t)
    g = jnp.asarray(rng.randint(0, 1 << 20, (n_tiles, m), dtype=np.int32))

    def point(tag, fn):
        timed = {}
        for form in ("oblivious", "gather"):
            timed[form] = bench(
                functools.partial(fn, oblivious=(form == "oblivious")))
        results[f"oblivious_vs_gather/{tag}/oblivious_s"] = round(timed["oblivious"], 5)
        results[f"oblivious_vs_gather/{tag}/gather_s"] = round(timed["gather"], 5)
        results[f"oblivious_vs_gather/{tag}/speedup"] = round(
            timed["gather"] / timed["oblivious"], 3)
        row(f"kernels/oblivious_vs_gather/{tag}/oblivious", timed["oblivious"],
            f"{timed['gather'] / timed['oblivious']:.2f}x vs gather")

    point(f"packed_positions/m={m}", lambda oblivious: kops.packed_tile_positions(
        ids, g, num_buckets=m, oblivious=oblivious))
    point(f"packed_fused/m={m}", lambda oblivious: kops.packed_fused_postscan_reorder(
        ids, g, keys, vals, num_buckets=m, oblivious=oblivious)[0])

    pair = BitfieldSpec(0, 8)
    point("fused2_fused/onehot/2r=8",
          lambda oblivious: kops.fused2_fused_postscan_reorder(
              keys, g, vals, spec=pair, split=4, oblivious=oblivious)[0])
    if not quick:
        point("fused2_fused/packed/2r=8",
              lambda oblivious: kops.fused2_fused_postscan_reorder(
                  keys, g, vals, spec=pair, split=4, family="packed",
                  oblivious=oblivious)[0])

    # RangeSpec: balanced-tree emit vs the legacy serialized compare chain
    flat = _keys()
    for s in (31, 255):
        spec = RangeSpec(tuple(int(x) for x in np.sort(
            rng.choice(2**30, size=s, replace=False)).tolist()))
        t_tree = bench(jax.jit(spec.emit_in_kernel), flat)
        t_chain = bench(jax.jit(spec._emit_chain), flat)
        tag = f"oblivious_vs_gather/rangespec/s={s}"
        results[f"{tag}/tree_s"] = round(t_tree, 5)
        results[f"{tag}/chain_s"] = round(t_chain, 5)
        results[f"{tag}/speedup"] = round(t_chain / t_tree, 3)
        row(f"kernels/rangespec/s={s}/tree-emit", t_tree,
            f"{t_chain / t_tree:.2f}x vs chain")

    return results


def main(quick: bool = False):
    if quick:
        run_packed_vs_onehot(quick=True)
        run_oblivious_vs_gather(quick=True)
        return
    run(key_value=False)
    run(key_value=True)
    run_distributions()
    run_fused_vs_legacy()
    run_batched_vs_host_loop()
    run_fused_labels_vs_materialized()
    run_packed_vs_onehot()
    run_oblivious_vs_gather()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--quick", action="store_true",
        help="only the packed-vs-onehot m=256 points (CI perf smoke)",
    )
    main(quick=ap.parse_args().quick)
