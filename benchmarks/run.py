"""Benchmark entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows.  Default mode keeps the FL
tables to 3 methods × 1 seed × 60 rounds (CPU-friendly); ``--full`` runs
all 9 methods × 2 seeds × 100 rounds (the EXPERIMENTS.md numbers).
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="run a single bench: accuracy|comm|rounds|roofline")
    args = ap.parse_args()

    from benchmarks import bench_accuracy, bench_comm, bench_rounds, roofline

    benches = {
        "accuracy": bench_accuracy.main,
        "comm": bench_comm.main,
        "rounds": bench_rounds.main,
        "roofline": roofline.main,
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        try:
            for row in fn(full=args.full):
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:
            failed.append(name)
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
