"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
configuration's file is the one the manifest gives; the traffic mix is
``bench/traffic/<traffic>.json``; every metric, end-to-end or per-layer, is
read by ``bench/metrics/<name>.py``. Adding any of them is a new file and a
manifest entry, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclass(frozen=True)
class Manifest:
    data: dict
    root: Path

    @classmethod
    def load(cls, root: Path = ROOT) -> "Manifest":
        with open(root / "BENCHMARK.json") as f:
            return cls(json.load(f), root)

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.data["workloads"])
        raise KeyError(f"unknown workload {name!r}; the manifest has: {known}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as f:
                    return json.load(f)
        raise KeyError(f"unknown config {name!r}")

    def traffic(self, name: str) -> dict:
        with open(self.traffic_file(name)) as f:
            return json.load(f)

    def traffic_file(self, name: str) -> Path:
        return self.root / "bench" / "traffic" / f"{name}.json"

    def metric_file(self, name: str) -> Path:
        return self.root / "bench" / "metrics" / f"{name}.py"

    def end_to_end(self, cell: str) -> List[dict]:
        """The end-to-end metrics ``cell`` reports."""
        return [
            m for m in self.data["end_to_end"] if cell in m.get("workloads", [cell])
        ]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics ``cell`` reports: those listing it, and those
        with no list whose end-to-end metric the cell reports."""
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [
            m
            for m in self.data["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)
        ]

    def reader(self, metric: str) -> ModuleType:
        """The module ``bench/metrics/<metric>.py``; its ``read(run)`` gives
        the metric's value, or None where the run holds nothing to read."""
        path = self.metric_file(metric)
        modname = "bench_metric_" + re.sub(r"\W", "_", metric)
        spec = importlib.util.spec_from_file_location(modname, path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
