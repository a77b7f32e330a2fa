"""Fixtures for the benchmark's own tests, which run on the CPU.

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and ``bench/``)
in a temporary directory with every shape cut small (a mix's ``rows``, a
configuration's grid ``N``), so a whole run of a cell takes about a second
here.

Nothing in these tests imports JAX while it is collected: pytest collects
``bench/`` before ``tests/``, whose ``conftest.py`` must set the host
device count before JAX first loads.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

#: Small sizes: one of table4's sizes for a one-system mix, and a grid of
#: 398 lines of 400 points: wide enough for the interleaved layout, and
#: stiff enough (mul1 = 640) that the bfloat16 control fails by 10x.
TINY_ROWS = 10000
TINY_GRID_N = 400


def copy_benchmark(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(
        REPO / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__")
    )
    return dst


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    root = copy_benchmark(tmp_path)
    for path in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        if "rows" in mix:
            mix["rows"] = TINY_ROWS
        mix["check_sample"] = 8
        path.write_text(json.dumps(mix))
    for path in (root / "bench" / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        if "N" in config["operands"]:
            config["operands"]["N"] = TINY_GRID_N
        path.write_text(json.dumps(config))
    return root
