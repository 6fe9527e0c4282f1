"""Finds a cell's parts by name: its entry in BENCHMARK.json, its
configuration file, its mix file and the reader of each per-layer metric.

Nothing here names a cell, a configuration, a mix or a metric: a new one is
a new file plus a new entry in BENCHMARK.json.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List


class Spec:
    def __init__(self, root: Path, here: Path):
        self.root, self.here = root, here
        self.bench = json.loads((root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == cell["config"]:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {cell['config']!r}")

    def mix(self, cell: dict) -> dict:
        return json.loads(
            (self.here / "mixes" / f"{cell['traffic']}.json").read_text())

    def peak(self, device_kind: str) -> dict:
        table = json.loads((self.here / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "peaks.json")
        return table[device_kind]

    def _metrics(self, key: str, cell: dict) -> List[dict]:
        out = []
        for m in self.bench[key]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                out.append(m)
        return out

    def end_to_end(self, cell: dict) -> List[dict]:
        return self._metrics("end_to_end", cell)

    def per_layer(self, cell: dict) -> List[dict]:
        return self._metrics("per_layer", cell)

    def readers(self, cell: dict) -> Dict[str, Callable]:
        """name -> read(rec) from `metrics/<name>.py` for each per-layer
        metric of the cell."""
        out = {}
        for m in self.per_layer(cell):
            path = self.here / "metrics" / f"{m['name']}.py"
            name = "metric_" + m["name"].replace(".", "_").replace("-", "_")
            out[m["name"]] = load(name, path).read
        return out


def load(name: str, path: Path):
    """The module in the file `path`, under `name` (once per file)."""
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
