"""Fixtures for the benchmark's own tests, which run on the CPU.

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and ``bench/``)
in a temporary directory with every cell cut small by its operand kind's
``tiny`` (``bench/operands/<kind>.py``), so a whole run of a cell takes
about a second here.

Nothing in these tests imports JAX while it is collected: pytest collects
``bench/`` before ``tests/``, whose ``conftest.py`` must set the host
device count before JAX first loads.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Iterable, Optional

import pytest

from bench.manifest import Manifest

REPO = Path(__file__).resolve().parents[2]


def copy_benchmark(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(
        REPO / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__")
    )
    return dst


def shrink(root: Path, cells: Optional[Iterable[str]] = None) -> None:
    """Cut the configuration and mix of each of ``cells`` (default: every
    cell of ``root``'s manifest) to its kind's ``tiny`` sizes, in place, and
    keep 8 outputs a run for the check."""
    manifest = Manifest.load(root)
    files = {c["name"]: root / c["file"] for c in manifest.data["configs"]}
    if cells is None:
        cells = [w["name"] for w in manifest.data["workloads"]]
    for name in cells:
        cell = manifest.workload(name)
        config = manifest.config(cell["config"])
        mix = manifest.traffic(cell["traffic"])
        kind = manifest.operands(config["operands"]["kind"])
        if hasattr(kind, "tiny"):
            config, mix = kind.tiny(config, mix)
        files[cell["config"]].write_text(json.dumps(config))
        manifest.traffic_file(cell["traffic"]).write_text(
            json.dumps({**mix, "check_sample": 8})
        )


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    root = copy_benchmark(tmp_path)
    shrink(root)
    return root
