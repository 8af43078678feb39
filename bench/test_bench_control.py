"""The control of ``correct``: the plain reference computed in bfloat16,
the precision below the configurations' float32, put in the program's
place; and the planted faults, put there the same way.  At a size a
test holds, on the CPU, each cell's limits pass the program and refuse
the control and each fault."""

import functools
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import check, spec  # noqa: E402
from calibrate import readings  # noqa: E402

TINY = json.loads((BENCH / "testdata" / "tiny.json").read_text())


@functools.cache
def _readings(cell: str) -> dict:
    c = spec.load_cell(cell)
    return {r["kind"]: r for r in readings(c, 2_147_483_647, True,
                                           TINY[c.config["name"]])}


@pytest.mark.parametrize("cell", spec.list_cells())
def test_the_limits_refuse_the_bfloat16_control(cell):
    got, limits = _readings(cell), spec.load_json("workloads", cell)["limits"]
    assert check.judge(got["program"], limits)[0], got["program"]
    assert not check.judge(got["control_bf16"], limits)[0], got["control_bf16"]


@pytest.mark.parametrize("cell", spec.list_cells())
def test_the_limits_refuse_the_planted_faults(cell):
    got, limits = _readings(cell), spec.load_json("workloads", cell)["limits"]
    assert not check.judge(got["fault_half_batch"], limits)[0], got["fault_half_batch"]
    assert not check.judge(got["fault_altered_selection"], limits)[0], \
        got["fault_altered_selection"]
