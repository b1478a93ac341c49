"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Emits ``name,us_per_call,derived`` CSV lines per benchmark plus the
raw tables each figure needs.  Scales are CPU-container-sized by
default; pass --paper-scale to use the paper's SCALE=20 (slow).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smallest scales (CI)")
    ap.add_argument("--paper-scale", action="store_true",
                    help="the paper's SCALE=20 sizes (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (affinity, bfs_algorithms, bfs_batched,
                            bfs_formats, bfs_layers, bfs_megakernel,
                            bfs_opt_ablation, bfs_packed,
                            bfs_persistent, bfs_plan_cache,
                            bfs_scaling, cost_drift, lm_roofline)

    # one provenance stamp per harness run (BENCH_bfs.json _meta)
    started = time.strftime("%Y-%m-%dT%H:%M:%S%z")

    layer_scale = 20 if args.paper_scale else (12 if args.quick else 16)
    abl_scale = 13 if not args.quick else 11
    scales = (11, 12) if args.quick else (12, 13, 14)

    benches = {
        "bfs_layers": lambda: bfs_layers.main(scale=layer_scale),
        "bfs_opt_ablation": lambda: bfs_opt_ablation.main(
            scale=abl_scale, n_roots=2 if args.quick else 3),
        "bfs_scaling": lambda: bfs_scaling.main(
            scales=scales, n_roots=2 if args.quick else 4),
        "bfs_batched": lambda: bfs_batched.main(
            scale=11 if args.quick else 12),
        "bfs_formats": lambda: bfs_formats.main(
            scale=10 if args.quick else 12),
        "bfs_packed": lambda: bfs_packed.main(
            scale=10 if args.quick else 11),
        "bfs_plan_cache": lambda: bfs_plan_cache.main(
            scale=9 if args.quick else 10),
        "bfs_megakernel": lambda: bfs_megakernel.main(
            scale=10 if args.quick else 12),
        "bfs_persistent": lambda: bfs_persistent.main(
            scale=10 if args.quick else 12),
        "bfs_algorithms": lambda: bfs_algorithms.main(
            scale=10 if args.quick else 12),
        "affinity": lambda: affinity.main(scale=abl_scale),
        "cost_drift": lambda: cost_drift.main(),
        "lm_roofline": lambda: lm_roofline.main(),
    }
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
            print(f"# {name} done in {time.perf_counter()-t0:.1f}s",
                  flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    # persist whatever succeeded: BENCH_bfs.json tracks the perf
    # trajectory (TEPS, analytic bytes-moved, active-tile counts)
    # across PRs; merge-update keeps other benchmarks' entries
    from benchmarks import common
    if common.RESULTS:
        common.save_results(meta=common.build_meta(timestamp=started))
        print(f"# wrote {len(common.RESULTS)} metrics (+_meta) to "
              f"{common.BENCH_JSON.name}")
    if failed:
        print(f"\nFAILED benchmarks: {failed}")
        sys.exit(1)
    print("\nALL BENCHMARKS OK")


if __name__ == "__main__":
    main()
