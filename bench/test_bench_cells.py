"""Each cell run end to end on the CPU at a size a test holds, through
the harness with the chip check skipped: a sound run is correct and
reports no device metric."""

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import harness, spec  # noqa: E402

TINY = json.loads((BENCH / "testdata" / "tiny.json").read_text())


def run(cell: str, seed: int) -> dict:
    config = spec.load_json("workloads", cell)["config"]
    return harness.run_cell(cell, seed, 0.3, False, t_start=time.perf_counter(),
                            require_tpu=False, overrides=TINY[config])


@pytest.mark.parametrize("cell", spec.list_cells())
def test_sound_run_is_correct(cell):
    out = run(cell, 2_147_483_659)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"] == {}  # no CPU number under a device metric's name
    assert list(out)[-1] == "checks"


def test_a_random_selection_cell_added_as_files_is_correct(tmp_path):
    """A FedAvg cell (uniform random selection, no poll) needs only a
    traffic file and a workload file: run end to end, it is correct,
    and a cohort other than the seed's draw is not."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "traffic" / "fedavg.compiled.json").write_text(json.dumps(
        {"name": "fedavg.compiled", "preset": "fedavg", "strategy": "random",
         "backend": "compiled", "fuse_rounds": 0, "warmup_rounds": 5}))
    wl = json.loads((root / "workloads" / "paper-mlp.compiled.json").read_text())
    wl.update(name="paper-mlp.fedavg", traffic="fedavg.compiled",
              limits={k: v for k, v in wl["limits"].items() if k != "poll_gap"})
    (root / "workloads" / "paper-mlp.fedavg.json").write_text(json.dumps(wl))
    out = harness.run_cell("paper-mlp.fedavg", 2_147_483_659, 0.3, False,
                           t_start=time.perf_counter(), root=root,
                           require_tpu=False, overrides=TINY["paper-mlp"])
    assert out["correct"], out["checks"]
    assert "poll_gap" not in out["checks"]

    from benchlib import reference

    cell = spec.load_cell("paper-mlp.fedavg", root)
    cell = spec.Cell(cell.name, cell.workload, {**cell.config, **TINY["paper-mlp"]},
                     cell.traffic, root)
    data = cell.model.make_data(cell.config, 3)
    own = harness.follow(cell, 3, data)
    altered = {r["round"]: {"selected": reference.swap_first(
        r["selected"], cell.config["n_clients"])} for r in own}
    ref = harness.follow(cell, 3, data, altered)
    assert not any(r["allowed"] for r in ref)
