"""The benchmark's files, found by name under its root directory.

    configs/<config>.json     one model configuration
    traffic/<traffic>.json    one round mix: preset, backend, mode
    workloads/<cell>.json     one cell: config, traffic, metrics, limits
    end_to_end/<metric>.py    one end-to-end metric: ``UNIT``, ``read(ctx)``
    metrics/<metric>.py       one per-layer metric: ``UNIT``, ``read(ctx)``
    models/<name>.py          one model family: data, weights, forward pass
    flops/<name>.py           model FLOPs of one round: ``round_flops``
    peaks.json                peak FLOP/s and HBM bytes/s by device kind

A later cell, configuration or metric is a new file here; no existing
file names it.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

__all__ = ["ROOT", "Cell", "SpecError", "load_cell", "load_json", "metric",
           "model", "flops_counter", "device_peaks", "list_cells"]


class SpecError(ValueError):
    """A benchmark file is missing or malformed."""


def _checked(name: str) -> str:
    if not _NAME.match(name or ""):
        raise SpecError(f"not a benchmark name: {name!r}")
    return name


def load_json(kind: str, name: str, root: Path = ROOT) -> dict:
    path = Path(root) / kind / f"{_checked(name)}.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                        f"named {name!r} ({path})") from None


@functools.cache
def _module(kind: str, name: str, root: Path):
    path = Path(root) / kind / f"{_checked(name)}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    root: Path = ROOT

    @property
    def strategy(self) -> str:
        """The selection rule the reference follows."""
        return self.traffic["strategy"]

    @property
    def model(self):
        """The module of the configuration's model family."""
        return model(self.config["model"], self.root)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    wl = load_json("workloads", name, root)
    cell = Cell(name, wl, load_json("configs", wl["config"], root),
                load_json("traffic", wl["traffic"], root), Path(root))
    for m in wl["end_to_end"]:
        metric("end_to_end", m, root)
    for m in wl["per_layer"]:
        metric("metrics", m, root)
    flops_counter(cell.config["flops"], root)
    model(cell.config["model"], root)
    return cell


def list_cells(root: Path = ROOT) -> list[str]:
    return sorted(p.stem for p in (Path(root) / "workloads").glob("*.json"))


def metric(kind: str, name: str, root: Path = ROOT):
    """The module of one metric (``kind`` is ``end_to_end`` or
    ``metrics``): its ``UNIT`` and ``read(ctx) -> float | None``, which
    returns ``None`` where it finds nothing to read."""
    return _module(kind, name, Path(root))


def model(name: str, root: Path = ROOT):
    """The module of one model family (``models/<name>.py``):
    ``make_data``, ``engine_kwargs``, ``init_params``, ``outputs`` and
    ``split_labels``."""
    return _module("models", name, Path(root))


def flops_counter(name: str, root: Path = ROOT):
    """``round_flops(config, strategy) -> dict`` of one model family."""
    return _module("flops", name, Path(root)).round_flops


def device_peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Peak rates of one chip; a device not in the table is an error."""
    table = json.loads((Path(root) / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json "
                        f"(known: {sorted(table)})")
    return table[device_kind]
