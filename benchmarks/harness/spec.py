"""Where the benchmark's files are, found by the names in BENCHMARK.json.

A cell is ``cells/<cell>.json``; it names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``).
Nothing here knows any cell, configuration or mix by name.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(kind: str, name: str) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    """(cell, configuration, traffic mix) for a workload of BENCHMARK.json.
    The cell's own file must agree with the workload's entry."""
    entry = next((w for w in benchmark()["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = _read("cells", name)
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(
                f"cells/{name}.json says {key}={cell[key]!r}, BENCHMARK.json "
                f"says {entry[key]!r}")
    return cell, _read("configs", cell["config"]), _read("traffic", cell["traffic"])


def metrics_for(name: str, group: str) -> List[Dict[str, Any]]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in benchmark()[group]
            if "workloads" not in m or name in m["workloads"]]
