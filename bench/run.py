"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are ``bench/workloads/*.json``.  The run makes its data and
weights from ``--seed``, builds the engine, warms up, and measures whole
federated rounds for ``--seconds`` (``--trace 1``: a shorter traced
window, for the per-layer metrics).  It then compares the rounds it ran
in set-up with the plain reference.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` (traced runs) and ``checks``, each number
compared beside its limit.  With no TPU, or fewer chips than the cell
needs, it prints no result and exits 3.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)

    from benchlib.harness import NoChip, enable_compile_cache, run_cell

    print(f"compile cache: {enable_compile_cache(BENCH.parent)}", file=sys.stderr)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START, keep_trace=args.keep_trace)
    except NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
