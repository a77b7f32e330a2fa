"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
configuration's file is the one the manifest gives; the traffic mix is
``bench/traffic/<traffic>.json``; the operand kind a configuration names
(``operands.kind``) is ``bench/operands/<kind>.py``, which makes the
operands, their float64 reference, the control and the bytes a row; every
metric, end-to-end or per-layer, is read by ``bench/metrics/<name>.py``.
Adding any of them is a new file and, for a configuration, mix or metric,
a manifest entry, never an edit.
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

    def operands_file(self, kind: str) -> Path:
        return self.root / "bench" / "operands" / f"{kind}.py"

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
        return load_module(self.metric_file(metric), "bench_metric_")

    def operands(self, kind: str) -> ModuleType:
        """The module ``bench/operands/<kind>.py``. It gives
        ``shape(config, traffic)``, the operand shape of every call;
        ``make(rng, index, shape, **params)``, one call's operands in the
        verb's order, any number of them; ``reference(*operands)``, the
        float64 solution, computed without the solver; and
        ``least_bytes_per_row(config)``, the bytes any implementation must
        move per row of the call shape. Optional: ``control(dtype)``, a
        drop-in verb that computes in ``dtype``, and ``tiny(config,
        traffic)``, the small sizes the CPU tests run the kind at."""
        return load_module(self.operands_file(kind), "bench_operands_")


def load_module(path: Path, prefix: str) -> ModuleType:
    modname = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
