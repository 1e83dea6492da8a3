import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick]

Modules (paper artifact -> bench):
  Fig 6 comm/compute breakdown of join  -> bench_join_breakdown
  Fig 7 OpenMPI vs Gloo vs UCX/UCC      -> bench_communicators
  Fig 8 strong scaling + pre-agg        -> bench_strong_scaling
  Fig 9 pipeline of operators           -> bench_pipeline
  §V-C serial performance               -> bench_local_ops
  kernels (interpret vs oracle)         -> bench_kernels
  beyond-paper MoE-dispatch-as-shuffle  -> bench_moe_shuffle
  sort-free vs sorted shuffle (PR 2)    -> bench_shuffle_impl
  adaptive skew mitigation (PR 10)      -> bench_skew

The 8-device XLA_FLAGS above is set before jax initializes (scaling
benches need parallelism); the dry-run (512 devices) is a separate entry
point, and unit tests see the plain 1-device backend.
"""

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="smaller row counts (CI-speed)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny row counts (seconds; CI sanity check only)")
    ap.add_argument("--csv", default="bench_results.csv")
    ap.add_argument("--json", default=None,
                    help="JSON artifact path (default BENCH_<scale>.json)")
    args = ap.parse_args()

    from . import (bench_communicators, bench_ingest, bench_join_breakdown,
                   bench_kernels, bench_local_ops, bench_moe_shuffle,
                   bench_pipeline, bench_shuffle_impl, bench_skew,
                   bench_strong_scaling)
    from .common import RESULTS, dump_csv, dump_json, enable_compile_cache

    enable_compile_cache()

    scale = 50 if args.smoke else 4 if args.quick else 1
    suites = {
        "local_ops": lambda: bench_local_ops.run(200_000 // scale),
        "communicators": lambda: bench_communicators.run(50_000 // scale),
        "join_breakdown": lambda: bench_join_breakdown.run(50_000 // scale),
        "strong_scaling": lambda: bench_strong_scaling.run(200_000 // scale),
        "pipeline": lambda: bench_pipeline.run(100_000 // scale),
        # floor: below ~4k rows/rank the dispatch overhead buries the delta
        "shuffle_impl": lambda: bench_shuffle_impl.run(
            max(4096, 65_536 // scale)),
        # out-of-core Fig-9 at 8x device capacity (asserts bit-identity)
        "out_of_core": lambda: bench_pipeline.run_oversub(
            max(4000, 100_000 // scale), oversub=8),
        # lazy DataFrame frontend overhead vs raw Plan (asserts bit-identity)
        "df_frontend": lambda: bench_pipeline.run_frontend(
            max(4000, 100_000 // scale)),
        # file ingest (repro.io): Parquet vs CSV vs read_numpy, 1x + 8x
        "ingest": lambda: bench_ingest.run(max(4000, 50_000 // scale)),
        # adaptive skew mitigation vs blind baseline (asserts bit-identity)
        "skew": lambda: bench_skew.run(max(8000, 160_000 // scale)),
        "kernels": bench_kernels.run if not args.quick else bench_kernels.run,
        "moe_shuffle": bench_moe_shuffle.run,
    }
    t0 = time.time()
    suite_seconds = {}
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} ===", flush=True)
        ts = time.time()
        fn()
        suite_seconds[name] = round(time.time() - ts, 3)
    total = time.time() - t0
    print(f"\n{len(RESULTS)} results in {total:.1f}s")
    dump_csv(args.csv)
    print(f"csv -> {args.csv}")
    scale_tag = "smoke" if args.smoke else "quick" if args.quick else "full"
    json_path = args.json or f"BENCH_{scale_tag}.json"
    dump_json(json_path, meta={"scale": scale_tag, "only": args.only,
                               "suite_seconds": suite_seconds,
                               "total_seconds": round(total, 3)})
    print(f"json -> {json_path}")


if __name__ == "__main__":
    main()
