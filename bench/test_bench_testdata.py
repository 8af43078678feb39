"""Every configuration has its CPU test sizes: in ``testdata/tiny.json``
or, for one added after it, in ``testdata/tiny.<configuration>.json``,
and never in both."""

import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
TINY = json.loads((BENCH / "testdata" / "tiny.json").read_text())
ADDED = {p.name[len("tiny."):-len(".json")]: json.loads(p.read_text())
         for p in (BENCH / "testdata").glob("tiny.*.json")}
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("config", CONFIGS)
def test_each_configuration_has_one_cpu_size(config):
    written = json.loads((BENCH / "testdata" / "tiny.json").read_text())
    assert (config in written) != (config in ADDED)


def test_added_sizes_reach_the_test_modules():
    """The root ``conftest.py`` adds them to this module's ``TINY`` as to the others'."""
    assert all(TINY.get(config) == sizes for config, sizes in ADDED.items())
