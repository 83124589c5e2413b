"""Finds the benchmark's pieces by name.

``BENCHMARK.json`` lists the cells and metrics.  A cell names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its correctness limits are in
``bench/limits/<cell>.json``; a per-layer metric's reader is
``bench/metrics/<metric>.py``.  Adding any of them is adding files.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class Registry:
    def __init__(self, root: Path = ROOT, bench: Path = BENCH):
        self.root, self.bench = Path(root), Path(bench)
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)

    def _json(self, kind: str, name: str) -> dict:
        path = self.bench / kind / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no {kind} named {name!r} ({path})")
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> Dict[str, float]:
        return self._json("limits", cell)["limits"]

    def metrics(self, cell: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        path = self.bench / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise KeyError(f"no reader for metric {metric!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
