"""The comparison that decides ``correct``.

The program's first rounds, run by the timed path in set-up, are set
against the plain reference's rounds from the same seed and data:

- ``poll_gap``          worst relative gap of a polled client loss
- ``select_diff``       rounds whose selected clients the selection rule
                        does not give: FedLECC's Algorithm 1 on the run's
                        own polled losses (checked by ``poll_gap``), or
                        on the reference's where a run shows none (fused
                        chunks); random selection has to draw them
                        exactly.  The reference trains the clients the
                        run selected, so that a choice between clients
                        tied to rounding does not part the two runs
- ``select_gap``        the least relative change of the reference's
                        polled losses under which Algorithm 1 picks
                        the run's cohort (``reference.fedlecc_reach``),
                        worst round: for a cell whose run shows no
                        polled losses (fused chunks poll on the device)
- ``train_loss_gap``    worst relative gap of a round's mean local loss
- ``first_change_gap``  worst leaf: the gap between the program's and
                        the reference's norm of the first round's change
                        of the global parameters (the update the server
                        applies), over the larger of the reference
                        leaf's norm and the median leaf's
- ``change_gap``        the same for the change after the last round
                        followed
- ``eval_loss_gap``     worst relative gap of the test loss
- ``eval_acc_gap``      worst absolute gap of the test accuracy

Leaves whose first change in the reference is under a thousandth of
the median leaf's are nought to rounding and are left out of both
change numbers.
"""

from __future__ import annotations

import numpy as np

__all__ = ["leaf_norms", "compare", "judge"]


def leaf_norms(params, base) -> np.ndarray:
    """Per-leaf Euclidean norm of ``params - base`` (float64, host)."""
    import jax

    return np.array([
        np.linalg.norm(np.asarray(p, np.float64) - np.asarray(b, np.float64))
        for p, b in zip(jax.tree.leaves(params), jax.tree.leaves(base))
    ])


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def _leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    scale = np.maximum(ref, np.median(ref))
    return float(np.max((np.abs(prog - ref) / scale)[keep]))


def compare(program: dict, reference: list[dict], base) -> dict:
    """``program``: round -> {selected, train_loss, [losses],
    [test_loss, test_acc], [change]} from the timed path; ``reference``:
    ``Reference.run`` records; ``base``: the initial parameters."""
    out: dict[str, float] = {}
    rounds = [rec["round"] for rec in reference]
    polled = [r for r in rounds if program[r].get("losses") is not None
              and reference[r]["losses"] is not None]
    if polled:
        out["poll_gap"] = max(_rel(program[r]["losses"], reference[r]["losses"])
                              for r in polled)
    out["select_diff"] = float(sum(not reference[r]["allowed"] for r in rounds))
    out["select_gap"] = max(reference[r]["select_gap"] for r in rounds)
    out["train_loss_gap"] = max(_rel(program[r]["train_loss"],
                                     reference[r]["train_loss"]) for r in rounds)
    ref_first = leaf_norms(reference[0]["params"], base)
    keep = ref_first >= 1e-3 * np.median(ref_first)
    out["first_change_gap"] = _leaf_gap(program[0]["change"], ref_first, keep)
    last = rounds[-1]
    ref_last = leaf_norms(reference[last]["params"], base)
    out["change_gap"] = _leaf_gap(program[last]["change"], ref_last, keep)
    evald = [r for r in rounds if "test_loss" in reference[r]]
    if evald:
        out["eval_loss_gap"] = max(_rel(program[r]["test_loss"],
                                        reference[r]["test_loss"]) for r in evald)
        out["eval_acc_gap"] = max(abs(program[r]["test_acc"] - reference[r]["test_acc"])
                                  for r in evald)
    return out


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number compared, its value beside its limit
    (a value that is no finite number is written as text, so that the
    result stays plain JSON).  The cell's limits name the numbers
    compared; a limit without its number is not correct."""
    values = {k: readings.get(k) for k in sorted(limits)}
    ok = all(v is not None and np.isfinite(v) and v <= limits[k]
             for k, v in values.items())
    checks = {k: {"value": v if v is None or np.isfinite(v) else str(v),
                  "limit": limits[k]} for k, v in values.items()}
    return ok, checks
