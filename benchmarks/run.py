"""Benchmark driver: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick defaults
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale settings
    PYTHONPATH=src python -m benchmarks.run \
        --json "$(python -m benchmarks.check_gates --next-name)"

Emits human tables plus CSV rows ``name,us_per_call,derived``; with
``--json`` the rows every bench reported through ``benchmarks.common.emit``
are aggregated into one machine-readable file — the next point of the
perf trajectory (``BENCH_<n>.json``).  ``benchmarks/check_gates.py`` names
the next point and gates it against the newest committed one; CI archives
the artifact per run.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale draws/steps/seeds (slow)")
    ap.add_argument("--only", default="",
                    help="comma list: unbiasedness,gradnorm,matrix,ratio,"
                         "efficiency,quality,rollout,async,packed,paged,"
                         "paged_learner,serving,dist,chaos,roofline")
    ap.add_argument("--json", default="",
                    help="write aggregated machine-readable results here")
    args = ap.parse_args()
    want = set(filter(None, args.only.split(",")))

    def on(name):
        return not want or name in want

    t0 = time.time()
    if on("unbiasedness"):
        from benchmarks import bench_unbiasedness
        bench_unbiasedness.run(draws=1500 if args.full else 400)
        print()
    if on("gradnorm"):
        from benchmarks import bench_gradnorm
        bench_gradnorm.run(draws=600 if args.full else 150)
        print()
    if on("matrix"):
        from benchmarks import bench_method_matrix
        bench_method_matrix.run(draws=400 if args.full else 100)
        print()
    if on("ratio"):
        from benchmarks import bench_selected_ratio
        bench_selected_ratio.run(steps=30 if args.full else 10)
        print()
    if on("efficiency"):
        from benchmarks import bench_efficiency
        bench_efficiency.run()
        print()
    if on("rollout"):
        from benchmarks import bench_rollout_throughput
        bench_rollout_throughput.run()
        print()
    if on("async"):
        from benchmarks import bench_async_overlap
        bench_async_overlap.run()
        print()
    if on("packed"):
        from benchmarks import bench_packed_learner
        bench_packed_learner.run()
        print()
    if on("paged"):
        from benchmarks import bench_paged_decode
        bench_paged_decode.run()
        print()
    if on("paged_learner"):
        from benchmarks import bench_paged_learner
        bench_paged_learner.run()
        print()
    if on("serving"):
        from benchmarks import bench_serving
        bench_serving.run()
        print()
    if on("dist"):
        from benchmarks import bench_dist_overlap
        bench_dist_overlap.run()
        print()
    if on("chaos"):
        from benchmarks import bench_fault_recovery
        bench_fault_recovery.run(smoke=not args.full)
        print()
    if on("quality"):
        from benchmarks import bench_quality
        bench_quality.run(steps=150 if args.full else 40,
                          seeds=(0, 1, 2, 3, 4) if args.full else (0, 1))
        print()
    if on("roofline"):
        # in this process: a child started after JAX is loaded here could
        # not take the chip this process holds
        from benchmarks import roofline
        roofline.main([])
    elapsed = time.time() - t0
    print(f"\n# benchmarks done in {elapsed:.0f}s")

    if args.json:
        import jax

        from benchmarks.common import RESULTS
        payload = {
            "schema": 1,
            "suite": sorted(want) if want else ["all"],
            "full": bool(args.full),
            "elapsed_s": round(elapsed, 1),
            "env": {
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                # thread-parallelism floors (async/overlap_speedup) are
                # meaningless on a single-CPU runner; check_gates reads
                # this to know whether they apply
                "cpu_count": os.cpu_count(),
            },
            "rows": RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(RESULTS)} rows to {args.json}")


if __name__ == "__main__":
    main()
