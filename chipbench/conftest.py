"""Cells the tiny benchmark of ``tests/test_chipbench_run.py`` has no stand-in for.

That module maps each committed cell named in a metric's ``workloads`` to
the tiny cell standing in for it (its ``CELLS``).  A cell with no stand-in
keeps its own name there: it is no cell of the tiny benchmark, so its
metrics go to no tiny cell.
"""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def pytest_collection_modifyitems(items):
    cells = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for mod in {getattr(item, "module", None) for item in items}:
        stand_ins = getattr(mod, "CELLS", None)
        if isinstance(stand_ins, dict) and mod.__name__.endswith("test_chipbench_run"):
            for cell in cells:
                stand_ins.setdefault(cell, cell)
