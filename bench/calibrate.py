"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --cell <cell> --seeds 1 2 3 ... \\
        [--control-seeds 1 2 3] [--out FILE]

For each seed, in one process: the program's first rounds through the
timed path (set-up and warm-up as a run makes them, no window), then the
plain reference, and the numbers ``correct`` compares.  For each
control seed also the control (the reference in bfloat16, the precision
below the configuration's float32) and two planted faults (half of every
local batch left out, the mean taken over the rest; the first selected
client swapped for the first unselected one), each compared with the
float32 reference.  One JSON line per reading: the lower reading of
a limit is the largest a sound run gives, the upper the smallest the
control or a fault gives.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))


def as_program(records, base) -> dict:
    """A reference run in the place of the program's first rounds."""
    from benchlib.check import leaf_norms

    out = {}
    for rec in records:
        r = {"selected": tuple(int(i) for i in rec["selected"]),
             "train_loss": rec["train_loss"], "losses": rec["losses"],
             "change": leaf_norms(rec["params"], base)}
        if "test_loss" in rec:
            r["test_loss"], r["test_acc"] = rec["test_loss"], rec["test_acc"]
        out[rec["round"]] = r
    return out


def readings(cell, seed: int, control: bool, overrides=None) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from benchlib import check, harness, spec

    if overrides:
        cell = spec.Cell(cell.name, cell.workload, {**cell.config, **overrides},
                         cell.traffic, cell.root)
    t0 = time.perf_counter()
    engine, rounds_it, hooks, program, data = harness.start(cell, seed)
    t_program = time.perf_counter() - t0
    del engine, rounds_it, hooks
    gc.collect()
    base = jax.device_get(cell.model.init_params(cell.config, seed))
    t0 = time.perf_counter()
    ref = harness.follow(cell, seed, data, program)
    out = [{"cell": cell.name, "seed": seed, "kind": "program",
            "program_s": t_program, "reference_s": time.perf_counter() - t0,
            **check.compare(program, ref, base)}]
    if control:
        for kind, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                         ("fault_half_batch", {"fault": "half_batch"}),
                         ("fault_altered_selection", {"fault": "altered_selection"})):
            other = as_program(harness.follow(cell, seed, data, **kw), base)
            ref = harness.follow(cell, seed, data, other)
            out.append({"cell": cell.name, "seed": seed, "kind": kind,
                        **check.compare(other, ref, base)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None, help="append the lines here too")
    args = ap.parse_args(argv)

    from benchlib import harness, spec

    harness.require_chips(1)
    harness.enable_compile_cache(BENCH.parent)
    cell = spec.load_cell(args.cell)
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        for line in readings(cell, seed, seed in args.control_seeds):
            text = json.dumps(line)
            print(text, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
