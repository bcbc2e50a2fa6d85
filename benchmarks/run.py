"""Benchmark harness: one section per paper table.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV. Roofline numbers (the per-arch
dry-run analysis) are produced by ``repro.launch.dryrun`` +
``benchmarks.roofline`` since they need the 512-virtual-device process.
"""

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sweeps")
    ap.add_argument("--section", action="append",
                    choices=["multisplit", "sort", "histogram", "sssp", "roofline",
                             "roofline-multisplit", "autotune-drift", "serving"])
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sections = args.section or ["multisplit", "sort", "histogram", "sssp",
                                "roofline", "roofline-multisplit",
                                "autotune-drift", "serving"]

    print("name,us_per_call,derived")
    if "multisplit" in sections:
        from benchmarks import bench_multisplit

        if args.quick:
            bench_multisplit.M_SWEEP = (8, 256)
        bench_multisplit.main()
    if "sort" in sections:
        from benchmarks import bench_sort

        bench_sort.main()
    if "histogram" in sections:
        from benchmarks import bench_histogram

        if args.quick:
            bench_histogram.M_SWEEP = (8, 256)
            bench_histogram.RANGE_M_SWEEP = (8, 64)
        bench_histogram.main()
    if "sssp" in sections:
        from benchmarks import bench_sssp

        bench_sssp.main()
    if "roofline" in sections:
        try:
            from benchmarks import roofline

            roofline.main()
        except Exception as e:  # artifacts may not exist yet
            print(f"# roofline table unavailable: {e}", file=sys.stderr)
    if "roofline-multisplit" in sections:
        from benchmarks import roofline_multisplit

        roofline_multisplit.main(quick=args.quick)
    if "autotune-drift" in sections:
        from benchmarks import autotune_drift

        autotune_drift.main(quick=args.quick)
    if "serving" in sections:
        from benchmarks import bench_serving

        bench_serving.main(quick=args.quick)


if __name__ == "__main__":
    main()
