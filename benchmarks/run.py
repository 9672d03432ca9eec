"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only convex,cnn,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced grids/steps (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (bench_cnn, bench_conflicts, bench_convex,
                            bench_dryrun, bench_qsgd, bench_step,
                            bench_theory, bench_wire)
    benches = {
        "theory": bench_theory.run,       # Lemma 3 / Theorem 4 / solver cost
        "convex": bench_convex.run,       # Figures 1-4
        "qsgd": bench_qsgd.run,           # Figures 5-6
        "cnn": bench_cnn.run,             # Figures 7-8
        "conflicts": bench_conflicts.run,  # Figure 9 (adapted; ex-"async")
        "wire": bench_wire.run,           # backend x wire pipeline costs
        "step": bench_step.run,           # sync vs overlapped exchange clock
        "dryrun": bench_dryrun.run,       # deliverables e+g tables
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
        except Exception:
            traceback.print_exc()
            print(f"{name},0.0,BENCH_ERROR")
            failed.append(name)
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.1f},{derived}", flush=True)
        print(f"{name}:total,{(time.time() - t0) * 1e6:.0f},wall", flush=True)
    if failed:
        print(f"benchmarks failed: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
