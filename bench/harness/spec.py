"""``BENCHMARK.json`` and the files it names, found by name.

A cell ``<config>.<traffic>`` is run from:

* ``bench/configs/<config>.json`` — the configuration as it is run;
* ``bench/traffic/<traffic>.json`` — the mix, whose ``driver`` key names
  ``bench/traffic/<driver>.py``, the generator that reads it;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")


def load_spec(path: str = SPEC_FILE) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_json(path: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import the Python file at ``path`` (relative to the checkout)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload entry with its configuration, traffic and metrics."""

    def __init__(self, spec: Dict[str, Any], name: str):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = load_json(configs[self.entry["config"]]["file"])
        self.traffic = load_json(
            f"bench/traffic/{self.entry['traffic']}.json")
        self.end_to_end = [m for m in spec["end_to_end"]
                           if self._applies(m)]
        self.per_layer = [m for m in spec["per_layer"] if self._applies(m)]

    def _applies(self, metric: Dict[str, Any]) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def driver(self) -> ModuleType:
        name = self.traffic["driver"]
        return load_module(f"bench/traffic/{name}.py", f"bench_driver_{name}")

    def readers(self) -> List[tuple]:
        """[(metric entry, reader module)] for this cell's per-layer
        metrics."""
        return [(m, load_module(f"bench/metrics/{m['name']}.py",
                                "bench_metric_" + m["name"].replace(".", "_")))
                for m in self.per_layer]
