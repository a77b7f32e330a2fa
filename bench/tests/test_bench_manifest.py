"""``BENCHMARK.json`` keeps to its contract, and everything it names is
found by name: configurations, traffic mixes and metric readers."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench.manifest import NAME, UNIT, Manifest

from .conftest import REPO

MANIFEST = Manifest.load(REPO)
DATA = MANIFEST.data

KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"}, {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"}, {"workloads"}),
}
LINE = re.compile(r"^[^\t\n]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def test_top_level_keys():
    assert set(DATA) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(DATA["paths"]) <= 16
    for p in DATA["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (REPO / p).is_dir()
    assert 1 <= len(DATA["command"]) <= 32
    assert all(LINE.match(w) for w in DATA["command"])
    named = [w for w in DATA["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in DATA["paths"]) for w in named)


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_keys_and_names(section):
    required, optional = KEYS[section]
    entries = DATA[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert required <= set(e) <= required | optional, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert LINE.match(e[key]), (e["name"], key)


def test_configs_resolve():
    used = {w["config"] for w in DATA["workloads"]}
    files = [c["file"] for c in DATA["configs"]]
    assert len(set(files)) == len(files)
    for c in DATA["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in DATA["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        cfg = MANIFEST.config(c["name"])
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert c["source"] == cfg["source"]


def test_workloads_resolve():
    pairs = [(w["config"], w["traffic"]) for w in DATA["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert 1 <= len(pairs) <= 24
    four = sum(w["chips"] == 4 for w in DATA["workloads"])
    assert four <= max(1, len(pairs) // 2)
    for w in DATA["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert MANIFEST.traffic_file(w["traffic"]).is_file()
        mix = MANIFEST.traffic(w["traffic"])
        assert mix["loop"] == "closed" and mix["verb"] in ("solve", "solve_batched")


@pytest.mark.parametrize(
    "metric", [m["name"] for m in DATA["end_to_end"] + DATA["per_layer"]]
)
def test_metric_reader_exists(metric):
    assert callable(MANIFEST.reader(metric).read)


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in DATA["end_to_end"]}
    assert "setup_s" in names and 1 <= len(names) <= 16
    for m in DATA["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough():
    for w in DATA["workloads"]:
        e2e = {m["name"] for m in MANIFEST.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert MANIFEST.per_layer(w["name"]), w["name"]


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"] for m in DATA["end_to_end"]}
    layers = {}
    for m in DATA["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        for cell in m.get("workloads", []):
            MANIFEST.workload(cell)
            assert m["moves"] in {x["name"] for x in MANIFEST.end_to_end(cell)}, (
                m["name"],
                cell,
            )
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all(re.match(r"^[^\t\n]{1,200}$", layer) for layer in layers)


def test_roofline_names():
    for m in DATA["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["name"].endswith("_roofline") or "mfu" in m["name"]
            assert m["unit"] == "%" and m["better"] == "higher"


def test_run_seconds_fit_a_full_check():
    rs = DATA["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_peaks_have_a_source():
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_files_under_paths_are_named_from_names():
    for p in DATA["paths"]:
        for f in (REPO / p).rglob("*"):
            if "__pycache__" in f.parts or f.is_dir():
                continue
            rel = f.relative_to(REPO).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_new_entries_need_no_edit(tiny_root: Path):
    """A new configuration, traffic mix and per-layer metric are new files
    plus manifest entries: the harness finds and runs them, and no file it
    had changes."""
    from bench.harness import load_peaks, run_cell

    before = {
        p: p.read_bytes() for p in (tiny_root / "bench").rglob("*") if p.is_file()
    }
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs" / "table4.json").read_text())
    cfg.update(name="dummy", operands={"kind": "diag_dominant", "dominance": 4.0})
    (bench / "configs" / "dummy.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "n4e4.json").read_text())
    mix["rows"] = 5000
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "dummy_calls.py").write_text(
        "def read(run):\n    return run.attempted\n"
    )
    manifest = json.loads((tiny_root / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "dummy", "source": cfg["source"], "file": "bench/configs/dummy.json",
         "reduced": [], "why": "a test entry"}
    )
    manifest["workloads"].append(
        {"name": "dummy.cell", "config": "dummy", "traffic": "dummy_mix", "chips": 1,
         "why": "a test entry"}
    )
    manifest["per_layer"].append(
        {"name": "dummy_calls", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "test", "moves": "solve_ms",
         "workloads": ["dummy.cell"]}
    )
    for m in manifest["end_to_end"]:
        if m["name"] == "solve_ms":
            m["workloads"].append("dummy.cell")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(manifest))

    res = run_cell(
        Manifest.load(tiny_root), "dummy.cell", 3, 0.2, True, 0.0,
        load_peaks("TPU v5 lite"),
    )
    assert res["correct"]
    assert res["metrics"]["dummy_calls"]["value"] == res["attempted"] > 0
    for p, content in before.items():
        assert p.read_bytes() == content, p
