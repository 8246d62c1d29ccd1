"""The system under test, as the benchmark reaches it.

Only this module and ``modes/`` import the program (``src/repro``).  A network
file of ``chipbench/networks/`` becomes the program's ``NetworkSpec`` here;
the reference reads the same file on its own.
"""

from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.bayesnet.compile import compile_network  # noqa: E402,F401
from repro.bayesnet.spec import NetworkSpec, Node  # noqa: E402
from repro.kernels import backend  # noqa: E402,F401


def spec(doc: dict) -> NetworkSpec:
    """The program's spec of one network file (nested per-value CPT rows)."""
    nodes = tuple(
        Node(n["name"], tuple(n["parents"]), tuple(tuple(r) for r in n["rows"]), k=int(n["k"]))
        for n in doc["nodes"]
    )
    return NetworkSpec(doc["name"], nodes, tuple(doc["evidence"]), tuple(doc["queries"]))
