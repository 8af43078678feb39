"""A run with the timed path broken underneath is not correct: once for
each fault a cell can have (a round that leaves the state unchanged,
half of each local batch left out, a selection altered where it is
made), each cell driven end to end on the CPU at a size a test holds.
The exchange between chips is no fault of these one-chip cells."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402
from test_bench_cells import run  # noqa: E402


def _unchanged(orig):
    def local_train(apply_fn, loss_fn, global_params, *args, **kwargs):
        return global_params, orig(apply_fn, loss_fn, global_params, *args, **kwargs)[1]
    return local_train


def _half_batch(orig):
    def local_train(*args, **kwargs):
        return orig(*args, **{**kwargs, "batch_size": kwargs["batch_size"] // 2})
    return local_train


def _swap_one(orig):
    """The mask with its first selected client swapped for the first
    unselected one."""
    def select(self, *args, **kwargs):
        mask = jnp.asarray(orig(self, *args, **kwargs))
        return mask.at[jnp.argmax(mask)].set(False).at[jnp.argmin(mask)].set(True)
    return select


def _break(monkeypatch, fault):
    import repro.engine.compiled as compiled
    from repro.core import strategies

    if fault in ("unchanged", "half_batch"):
        wrap = _unchanged if fault == "unchanged" else _half_batch
        monkeypatch.setattr(compiled, "local_train", wrap(compiled.local_train))
    else:
        for cls in (strategies.FedLECC, strategies.UniformRandom):
            for name in ("select_mask_jax", "select_mask_traced"):
                monkeypatch.setattr(cls, name, _swap_one(getattr(cls, name)))


FAULTS = [(cell, fault) for cell in spec.list_cells()
          for fault in ("unchanged", "half_batch", "altered_selection")
          # a cell that compares no selection cannot see an altered one
          if fault != "altered_selection"
          or {"select_diff", "select_gap"} & set(spec.load_json("workloads", cell)["limits"])]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    _break(monkeypatch, fault)
    jax.clear_caches()  # the jitted rounds must trace the broken path
    try:
        out = run(cell, 2_147_483_659)
    finally:
        jax.clear_caches()
    assert not out["correct"], out["checks"]
