"""Operand kinds are files found by name: each defines the interface, none
imports the solver, the two kinds the cells use make the pools they always
made, and a new kind, of any number of operands and bytes a row, is new
files plus manifest entries only."""

from __future__ import annotations

import ast
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import systems
from bench.harness import Run, load_peaks, run_cell
from bench.manifest import Manifest

from .conftest import REPO, shrink

MANIFEST = Manifest.load(REPO)
CELLS = [w["name"] for w in MANIFEST.data["workloads"]]
KIND_FILES = sorted(
    p for p in (REPO / "bench" / "operands").glob("*.py") if p.name != "__init__.py"
)
REQUIRED = ("shape", "make", "reference", "least_bytes_per_row")
OPTIONAL = ("control", "tiny")
DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("path", KIND_FILES, ids=lambda p: p.stem)
def test_kind_defines_the_interface(path: Path):
    kind = MANIFEST.operands(path.stem)
    for name in REQUIRED:
        assert callable(getattr(kind, name, None)), name
    for name in OPTIONAL:
        assert not hasattr(kind, name) or callable(getattr(kind, name)), name


@pytest.mark.parametrize(
    "path", KIND_FILES + [REPO / "bench" / "systems.py"], ids=lambda p: p.stem
)
def test_kind_imports_nothing_of_the_solver(path: Path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""]
        else:
            continue
        assert not any(n == "repro" or n.startswith("repro.") for n in names), path


def test_every_config_names_a_kind_file():
    for c in MANIFEST.data["configs"]:
        kind = MANIFEST.config(c["name"])["operands"]["kind"]
        assert MANIFEST.operands_file(kind).is_file(), (c["name"], kind)


def pool_digest(pool) -> str:
    h = hashlib.sha256()
    for ops in pool:
        for a in ops:
            h.update(str(a.dtype).encode())
            h.update(repr(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


#: sha256 of ``make_pool``'s arrays (a pool of 4) as the generators made
#: them before they moved into ``bench/operands/``.
POOL_DIGESTS = {
    ("table4", "rows", 4000, 7):
        "3410219b63144aa7c55eaff0d4a338c8e7aed8671472021721d43fe0f231cfd6",
    ("table4", "rows", 4000, 2**31 + 977):
        "cf9fab2ae991f01b29e4a4421ac021db8105e2cc756e2eba7627546be43d46ab",
    ("table4", "rows", 40000, 7):
        "aece453c6ef297ba0973f63d378ac0813938705f25303c65cb2b91b633f5ff0e",
    ("table4", "rows", 40000, 2**31 + 977):
        "ebd90cf2224eb3eef7229adf4a4cdfb222b4e74ac5e62829597a8ad5cc498279",
    ("adi_heat2d", "N", 400, 7):
        "4332a1b51ea7669b7785bbab481025ddbcaf5051a9421e418835bdf4f6ea41bc",
    ("adi_heat2d", "N", 400, 2**31 + 977):
        "7bafa54f2244960b617224f11d65d7ef6dc2848652b14f9153a5aabd7cbeae00",
    ("adi_heat2d", "N", 1000, 7):
        "8ec7c3ff6fe419a6d8172f3ac61683f09bf5df3c64f797c2e41588103c0d9221",
    ("adi_heat2d", "N", 1000, 2**31 + 977):
        "daba11668d6d57250830c9eba72641b7068e712ebbef353ea7afb7dfc5ec9c4e",
}


@pytest.mark.parametrize("key", sorted(POOL_DIGESTS), ids=lambda k: "-".join(map(str, k)))
def test_pools_are_byte_identical(key):
    name, size_key, size, seed = key
    config = MANIFEST.config(name)
    traffic = {"pool": 4}
    if size_key == "rows":
        traffic["rows"] = size
    else:
        config["operands"][size_key] = size
    kind = MANIFEST.operands(config["operands"]["kind"])
    assert pool_digest(systems.make_pool(kind, config, traffic, seed)) == POOL_DIGESTS[key]


@pytest.fixture(scope="module")
def red():
    """The reduction of a trace recorded on a v5e."""
    from bench import trace

    return trace.reduce(trace.load(str(DATA / "v5e_spans.xplane.pb")))


@pytest.mark.parametrize("cell", CELLS)
def test_solve_roofline_is_the_20_byte_formula(red, cell: str):
    """The reader, fed the kind's bytes a row, reads what its old constant
    of 20 bytes a row gave on the recorded v5e trace's reduction."""
    w = MANIFEST.workload(cell)
    config, traffic = MANIFEST.config(w["config"]), MANIFEST.traffic(w["traffic"])
    kind = MANIFEST.operands(config["operands"]["kind"])
    peaks = load_peaks("TPU v5 lite")
    run = Run(
        cell=cell, config=config, traffic=traffic, peaks=peaks,
        shape=kind.shape(config, traffic),
        least_bytes_per_row=kind.least_bytes_per_row(config), reduction=red,
    )
    assert kind.least_bytes_per_row(config) == 20
    rows = int(np.prod(run.shape))
    parent = 100.0 * (rows * 20 / peaks["hbm_bytes_per_s"]) / (red.busy_ns / red.calls / 1e9)
    assert MANIFEST.reader("solve_roofline").read(run) == parent


def test_solve_roofline_takes_the_kinds_bytes(red):
    """28 bytes a row (six fp32 words in, one out, as a pentadiagonal kind
    moves) reads 28/20 of what 20 bytes a row reads."""
    reader = MANIFEST.reader("solve_roofline")
    peaks = load_peaks("TPU v5 lite")

    def read(nbytes):
        return reader.read(Run(cell="c", config={}, traffic={}, peaks=peaks,
                               shape=(8, 48), least_bytes_per_row=nbytes, reduction=red))

    assert read(28) == pytest.approx(read(20) * 28 / 20, rel=1e-15)
    assert read(0) is None


# ---------------------------------------------------------- new kinds --
CONST_TRIDIAG = '''\
"""Constant-diagonal tridiagonal systems, a batch of them per call."""
import numpy as np


def shape(config, traffic):
    return (int(traffic["systems"]), int(config["operands"]["n"]))


def make(rng, index, shape, n, lower, diag, upper):
    dl, d, du = (np.full(shape, v) for v in (lower, diag, upper))
    dl[:, 0] = du[:, -1] = 0.0
    return dl, d, du, rng.standard_normal(shape)


def reference(dl, d, du, b):
    dl, d, du, b = (np.asarray(a, dtype=np.float64) for a in (dl, d, du, b))
    i = np.arange(d.shape[-1])
    a = np.zeros(d.shape + i.shape)
    a[:, i, i] = d
    a[:, i[1:], i[:-1]] = dl[:, 1:]
    a[:, i[:-1], i[1:]] = du[:, :-1]
    return np.linalg.solve(a, b[..., None])[..., 0]


def least_bytes_per_row(config):
    return 20


def tiny(config, traffic):
    return {**config, "operands": {**config["operands"], "n": 80}}, {**traffic, "systems": 16}
'''

PENTA = '''\
"""Pentadiagonal systems, six operands a call: the coefficients of
x[i-2], x[i-1], x[i], x[i+1], x[i+2], then b."""
import numpy as np


def shape(config, traffic):
    return (int(traffic["systems"]), int(config["operands"]["n"]))


def make(rng, index, shape, n, dominance):
    l2, l1, u1, u2 = (rng.uniform(-1.0, 1.0, size=shape) for _ in range(4))
    l2[:, :2] = l1[:, :1] = u1[:, -1:] = u2[:, -2:] = 0.0
    d = dominance * (abs(l2) + abs(l1) + abs(u1) + abs(u2)) + 1.0
    return l2, l1, d, u1, u2, rng.standard_normal(shape)


def reference(l2, l1, d, u1, u2, b):
    from scipy.linalg import solve_banded

    l2, l1, d, u1, u2, b = (np.asarray(a, dtype=np.float64) for a in (l2, l1, d, u1, u2, b))
    x = np.empty(b.shape)
    for k in range(b.shape[0]):
        ab = np.zeros((5, b.shape[1]))
        ab[0, 2:], ab[1, 1:], ab[2] = u2[k, :-2], u1[k, :-1], d[k]
        ab[3, :-1], ab[4, :-2] = l1[k, 1:], l2[k, 2:]
        x[k] = solve_banded((2, 2), ab, b[k])
    return x


def least_bytes_per_row(config):
    return 28


def tiny(config, traffic):
    return {**config, "operands": {**config["operands"], "n": 48}}, {**traffic, "systems": 8}
'''


def dense_penta(l2, l1, d, u1, u2, b):
    """A numpy stand-in for a pentadiagonal verb: the dense solve."""
    i = np.arange(d.shape[-1])
    a = np.zeros(d.shape + i.shape)
    a[:, i, i] = d
    a[:, i[1:], i[:-1]] = l1[:, 1:]
    a[:, i[2:], i[:-2]] = l2[:, 2:]
    a[:, i[:-1], i[1:]] = u1[:, :-1]
    a[:, i[:-2], i[2:]] = u2[:, :-2]
    x = np.linalg.solve(a, np.asarray(b, dtype=np.float64)[..., None])[..., 0]
    return x.astype(np.float32)


def altered_penta(*ops):
    x = dense_penta(*ops)
    x[0, 5] += 1.0
    return x


#: kind -> (its module's text, its parameters, the verb the window drives,
#: rows a tiny call holds, bytes a row)
NEW_KINDS = {
    "const_tridiag": (CONST_TRIDIAG, {"lower": -1.0, "diag": 4.0, "upper": -1.0},
                      None, 16 * 80, 20),
    "penta": (PENTA, {"dominance": 2.0}, lambda s, name: dense_penta, 8 * 48, 28),
}


def add_kind_cell(root: Path, kind: str, params: dict) -> str:
    """Write kind ``kind``, a configuration at full size, a mix, a
    per-layer metric and their manifest entries into ``root``; cut the new
    cell by the kind's ``tiny``; return the cell's name."""
    bench = root / "bench"
    table4 = json.loads((bench / "configs" / "table4.json").read_text())
    config = {
        "name": kind, "source": "a test entry",
        "operands": {"kind": kind, "n": 100000, **params},
        "dtype": "float32", "x64": True, "solver": table4["solver"],
        "check": {"max_rel_err": 1e-4}, "reduced": {},
    }
    (bench / "configs" / f"{kind}.json").write_text(json.dumps(config))
    mix = {"loop": "closed", "verb": "solve_batched", "systems": 4096, "pool": 2,
           "warm_calls": 2, "check_sample": 64, "trace_seconds": 0.1}
    (bench / "traffic" / f"{kind}_mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "probe_bytes.py").write_text(
        "def read(run):\n    return run.rows_per_call * run.least_bytes_per_row\n"
    )
    cell = f"{kind}.batch"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": kind, "source": "a test entry", "file": f"bench/configs/{kind}.json",
         "reduced": [], "why": "a test entry"}
    )
    manifest["workloads"].append(
        {"name": cell, "config": kind, "traffic": f"{kind}_mix", "chips": 1,
         "why": "a test entry"}
    )
    manifest["per_layer"].append(
        {"name": "probe_bytes", "unit": "B", "better": "lower",
         "source": "host_clock", "layer": "test", "moves": "solve_ms",
         "workloads": [cell]}
    )
    for m in manifest["end_to_end"]:
        if m["name"] == "solve_ms":
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    shrink(root, [cell])
    return cell


@pytest.mark.parametrize("kind", sorted(NEW_KINDS))
def test_new_operand_kind_needs_no_edit(tiny_root: Path, kind: str):
    """A new kind is a new file under ``bench/operands/``: the harness
    makes its operands, shrinks, checks and counts its bytes through it,
    whatever its number of operands, and no file the benchmark had
    changes."""
    text, params, verb_for, rows, bytes_per_row = NEW_KINDS[kind]
    bench = tiny_root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "operands" / f"{kind}.py").write_text(text)
    cell = add_kind_cell(tiny_root, kind, params)

    manifest = Manifest.load(tiny_root)
    peaks = load_peaks("TPU v5 lite")
    res = run_cell(manifest, cell, 11, 0.2, True, 0.0, peaks, verb_for=verb_for)
    assert res["correct"], res["check"]
    assert res["check"]["outputs_compared"]["value"] > 0
    assert res["metrics"]["probe_bytes"]["value"] == rows * bytes_per_row
    if kind == "penta":
        bad = run_cell(manifest, cell, 11, 0.2, False, 0.0, peaks,
                       verb_for=lambda s, name: altered_penta)
        assert bad["correct"] is False, bad["check"]
    for p, content in before.items():
        assert p.read_bytes() == content, p
