"""CPU sizes of the benchmark configurations that
``bench/testdata/tiny.json`` predates.

``tiny.json`` holds the sizes at which the benchmark's tests run each
configuration on the CPU, and stays as it was written.  A configuration
added later brings its sizes in a file of its own,
``bench/testdata/tiny.<configuration>.json``; this hook adds each such
file to the ``TINY`` table of each test module in ``bench/``, the
modules that read ``tiny.json``.

It sits at the root, not in ``bench/``: the modules of ``tests/`` import
``tests/conftest.py`` as ``conftest``, which a second ``conftest.py``
loaded after it would shadow."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent / "bench"
TESTDATA = BENCH / "testdata"


def added_sizes() -> dict:
    return {p.name[len("tiny."):-len(".json")]: json.loads(p.read_text())
            for p in sorted(TESTDATA.glob("tiny.*.json"))}


def pytest_collection_modifyitems(items):
    added = added_sizes()
    modules = {item.module for item in items if getattr(item, "module", None)}
    for module in modules:
        tiny = getattr(module, "TINY", None)
        if Path(module.__file__).resolve().parent == BENCH and isinstance(tiny, dict):
            for config, sizes in added.items():
                tiny.setdefault(config, sizes)
