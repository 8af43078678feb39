"""The benchmark's files: found by name, consistent with BENCHMARK.json,
and the FLOPs counts against hand counts.  CPU only, no chip."""

import json
import math
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", spec.list_cells())
def test_every_cell_names_files_that_exist(cell):
    c = spec.load_cell(cell)
    assert c.workload["name"] == cell
    assert c.config["name"] == c.workload["config"]
    assert c.traffic["name"] == c.workload["traffic"]
    assert c.workload["chips"] in (1, 4)
    for name in c.workload["limits"]:
        assert name in ("poll_gap", "select_diff", "select_gap", "train_loss_gap",
                        "first_change_gap", "change_gap", "eval_loss_gap",
                        "eval_acc_gap")


def test_benchmark_json_matches_the_files():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert sorted(cells) == spec.list_cells()
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for name, w in cells.items():
        wl = spec.load_json("workloads", name)
        assert (w["config"], w["traffic"], w["chips"]) == (
            wl["config"], wl["traffic"], wl["chips"])
        assert configs[w["config"]]["file"] == f"bench/configs/{w['config']}.json"
    for kind, key in (("end_to_end", "end_to_end"), ("metrics", "per_layer")):
        for m in BENCHMARK[key]:
            assert spec.metric(kind, m["name"]).UNIT == m["unit"]
            reporting = [c for c in cells if m["name"] in spec.load_json(
                "workloads", c)[key]]
            assert sorted(m.get("workloads", list(cells))) == sorted(reporting)
    for c in BENCHMARK["configs"]:
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"]


def test_unknown_device_kind_is_an_error():
    assert spec.device_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError, match="not in peaks.json"):
        spec.device_peaks("TPU v9 imaginary")


def test_a_new_cell_config_and_metric_are_found_by_name(tmp_path):
    """Adding files, and editing none, is enough for the harness."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "configs" / "paper-mlp.json").read_text())
    cfg.update(name="paper-mlp-wide", hidden=[400, 400])
    (root / "configs" / "paper-mlp-wide.json").write_text(json.dumps(cfg))
    (root / "metrics" / "rounds_traced.py").write_text(
        'UNIT = "rounds"\n\n\ndef read(ctx):\n    return ctx["rounds"] or None\n')
    wl = json.loads((root / "workloads" / "paper-mlp.compiled.json").read_text())
    wl.update(name="paper-mlp-wide.compiled", config="paper-mlp-wide",
              per_layer=["rounds_traced"])
    (root / "workloads" / "paper-mlp-wide.compiled.json").write_text(json.dumps(wl))

    assert "paper-mlp-wide.compiled" in spec.list_cells(root)
    cell = spec.load_cell("paper-mlp-wide.compiled", root)
    assert cell.config["hidden"] == [400, 400]
    reader = spec.metric("metrics", "rounds_traced", root)
    assert (reader.UNIT, reader.read({"rounds": 7})) == ("rounds", 7)
    flops = spec.flops_counter(cell.config["flops"], root)(cell.config, "fedlecc")
    assert flops["total"] > spec.flops_counter("mlp")(
        json.loads((BENCH / "configs" / "paper-mlp.json").read_text()), "fedlecc")["total"]
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_new_model_family_is_found_by_name(tmp_path):
    """A configuration names its model family; the family's data,
    weights and forward pass are one new file under ``models/``."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "models" / "wide.py").write_text(
        (root / "models" / "mlp.py").read_text().replace(
            'return {"hidden": tuple(cfg["hidden"])}',
            'return {"hidden": tuple(2 * h for h in cfg["hidden"])}'))
    cfg = json.loads((root / "configs" / "paper-mlp.json").read_text())
    cfg.update(name="paper-wide", model="wide")
    (root / "configs" / "paper-wide.json").write_text(json.dumps(cfg))
    wl = json.loads((root / "workloads" / "paper-mlp.compiled.json").read_text())
    wl.update(name="paper-wide.compiled", config="paper-wide")
    (root / "workloads" / "paper-wide.compiled.json").write_text(json.dumps(wl))

    cell = spec.load_cell("paper-wide.compiled", root)
    assert cell.model.engine_kwargs(cell.config)[0]["hidden"] == (400, 400)
    assert spec.load_cell("paper-mlp.compiled", root).model.engine_kwargs(
        cfg)[0]["hidden"] == (200, 200)
    cfg.update(model="no-such-family")
    (root / "configs" / "paper-wide.json").write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="no models module"):
        spec.load_cell("paper-wide.compiled", root)
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_missing_name_is_an_error(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_json("configs", "../BENCHMARK")


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_mlp_round_flops_match_the_hand_count():
    params = 784 * 200 + 200 + 200 * 200 + 200 + 200 * 10 + 10
    assert params == 199_210
    f = spec.flops_counter("mlp")(_cfg("paper-mlp"), "fedlecc")
    # 10 clients x 10 steps x 64 samples trained, 100 x 128 polled,
    # 10 000 test samples every 5th round
    assert f["train"] == 6 * params * 6_400
    assert f["poll"] == 2 * params * 12_800
    assert f["eval"] == 2 * params * 10_000 / 5
    assert math.isclose(f["total"], 13.546e9, rel_tol=1e-3)
    assert spec.flops_counter("mlp")(_cfg("paper-mlp"), "random")["poll"] == 0
