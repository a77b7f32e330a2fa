"""Whole runs: the command refuses a host without a TPU or without the
solver; on the CPU, past the look for a chip, a run is correct with the
solver in place and not correct with the timed path broken underneath or
with the bfloat16 control in its place."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import systems
from bench.harness import Reservoir, load_peaks, run_cell
from bench.manifest import Manifest

from .conftest import REPO, copy_benchmark

MANIFEST = Manifest.load(REPO)
CELLS = [w["name"] for w in MANIFEST.data["workloads"]]
SEED = 2**31 + 977  # wider than 32 signed bits, as a run's --seed may be
diag_dominant = MANIFEST.operands("diag_dominant")
polybench_adi = MANIFEST.operands("polybench_adi")


def cell_kind(cell: str):
    """The operand kind of ``cell``'s configuration."""
    config = MANIFEST.config(MANIFEST.workload(cell)["config"])
    return MANIFEST.operands(config["operands"]["kind"])


def command(cwd: Path, *extra: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_refuses_without_tpu():
    out = command(REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_refuses_without_the_solver(tmp_path: Path):
    out = command(copy_benchmark(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def run_tiny(root: Path, cell: str, verb_for=None, trace: bool = False) -> dict:
    return run_cell(
        Manifest.load(root), cell, SEED, 0.3, trace, 0.0,
        load_peaks("TPU v5 lite"), verb_for=verb_for,
    )


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root: Path, cell: str):
    res = run_tiny(tiny_root, cell)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert "setup_s" in res["metrics"]


def altered(x: np.ndarray) -> np.ndarray:
    """One answer altered where it is produced."""
    x = np.array(x)
    x.reshape(-1)[x.size // 3] += 1.0
    return x


def half_left_out(x: np.ndarray) -> np.ndarray:
    """Half of the batch (half the systems, or half the rows of one) left
    out of the solve: zeros where they should be."""
    x = np.array(x)
    if x.ndim == 2:
        x[x.shape[0] // 2 :] = 0.0
    else:
        x[x.shape[-1] // 2 :] = 0.0
    return x


def raises_after_warm_up(verb):
    """Calls fail once set-up is over: a failed call counts as missing."""
    calls = []

    def broken(*ops):
        calls.append(1)
        if len(calls) > 2:
            raise RuntimeError("solve failed")
        return verb(*ops)

    return broken


FAULTS = {
    "answer_altered": lambda verb: lambda *ops: altered(verb(*ops)),
    "half_left_out": lambda verb: lambda *ops: half_left_out(verb(*ops)),
    "state_unchanged": lambda verb: lambda *ops: np.array(ops[-1]),
    "call_raises": raises_after_warm_up,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root: Path, cell: str, fault: str):
    res = run_tiny(
        tiny_root, cell, verb_for=lambda s, name: FAULTS[fault](getattr(s, name))
    )
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(tiny_root: Path, cell: str):
    control = cell_kind(cell).control("bfloat16")
    res = run_tiny(tiny_root, cell, verb_for=lambda s, name: control)
    assert res["correct"] is False
    err = res["check"]["max_rel_err"]
    assert err["value"] > 10 * err["limit"]  # room below the upper reading


def test_float32_thomas_stands_in_correctly(tiny_root: Path):
    """The control's own algorithm at the configurations' precision passes:
    what fails the control is the precision, not the code."""
    control = cell_kind(CELLS[0]).control("float32")
    res = run_tiny(tiny_root, CELLS[0], verb_for=lambda s, name: control)
    assert res["correct"], res["check"]


def test_trace_run_on_cpu_reports_counters(tiny_root: Path):
    res = run_tiny(tiny_root, CELLS[0], trace=True)
    assert res["correct"]
    assert res["metrics"]["window_compiles"]["value"] == 0
    # no TPU plane on the CPU: no device metric is made up
    assert "device_idle_pct" not in res["metrics"]


def test_reservoir_is_uniform_and_seeded():
    def picks(seed):
        r = Reservoir(8, np.random.default_rng(seed))
        for i in range(5000):
            r.offer(i, i)
        return sorted(i for i, _ in r.items)

    assert picks(1) == picks(1) and picks(1) != picks(2)
    assert len(picks(1)) == 8
    counts = np.zeros(10)
    for s in range(300):
        for i in picks(s):
            counts[i * 10 // 5000] += 1
    assert counts.min() > 0.6 * counts.mean()


def test_reference_matches_dense_solve():
    rng = np.random.default_rng(0)
    ops = diag_dominant.make(rng, 0, (3, 50), 2.5)
    x = diag_dominant.reference(*ops)
    dl, d, du, b = ops
    for k in range(3):
        a = np.diag(d[k]) + np.diag(dl[k, 1:], -1) + np.diag(du[k, :-1], 1)
        np.testing.assert_allclose(x[k], np.linalg.solve(a, b[k]), rtol=1e-12)


ADI = {"name": "adi", "dtype": "float32",
       "operands": {"kind": "polybench_adi", "N": 30, "TSTEPS": 20, "B1": 2.0, "B2": 1.0}}
SWEEPS = {"pool": 2}


def test_pool_is_seeded():
    a = systems.make_pool(polybench_adi, ADI, SWEEPS, SEED)
    b = systems.make_pool(polybench_adi, ADI, SWEEPS, SEED)
    c = systems.make_pool(polybench_adi, ADI, SWEEPS, SEED + 1)
    assert all(np.array_equal(x, y) for p, q in zip(a, b) for x, y in zip(p, q))
    assert not np.array_equal(a[0][3], c[0][3])
    assert not np.array_equal(a[0][3], a[1][3])
    assert a[0][1].dtype == np.float32
    assert a[0][1].shape == (28, 30)


def polybench_coefficients(n, tsteps):
    dx = dy = 1.0 / n
    dt = 1.0 / tsteps
    mul1 = 2.0 * dt / (dx * dx)
    mul2 = 1.0 * dt / (dy * dy)
    a = c = -mul1 / 2.0
    d = f = -mul2 / 2.0
    return a, 1.0 + mul1, c, d, 1.0 + mul2, f


def polybench_column_sweep(u, tsteps):
    """PolyBench/C 4.2's ``kernel_adi`` column sweep, transcribed loop by
    loop: v from u."""
    n = u.shape[0]
    a, b, c, d, e, f = polybench_coefficients(n, tsteps)
    v, p, q = (np.zeros_like(u) for _ in range(3))
    for i in range(1, n - 1):
        v[0][i] = 1.0
        p[i][0] = 0.0
        q[i][0] = v[0][i]
        for j in range(1, n - 1):
            p[i][j] = -c / (a * p[i][j - 1] + b)
            q[i][j] = (
                -d * u[j][i - 1] + (1.0 + 2.0 * d) * u[j][i] - f * u[j][i + 1]
                - a * q[i][j - 1]
            ) / (a * p[i][j - 1] + b)
        v[n - 1][i] = 1.0
        for j in range(n - 2, 0, -1):
            v[j][i] = p[i][j] * v[j + 1][i] + q[i][j]
    return v


def polybench_row_sweep(v, tsteps):
    """PolyBench/C 4.2's ``kernel_adi`` row sweep, transcribed: u from v."""
    n = v.shape[0]
    a, b, c, d, e, f = polybench_coefficients(n, tsteps)
    u, p, q = (np.zeros_like(v) for _ in range(3))
    for i in range(1, n - 1):
        u[i][0] = 1.0
        p[i][0] = 0.0
        q[i][0] = u[i][0]
        for j in range(1, n - 1):
            p[i][j] = -f / (d * p[i][j - 1] + e)
            q[i][j] = (
                -a * v[i - 1][j] + (1.0 + 2.0 * a) * v[i][j] - c * v[i + 1][j]
                - d * q[i][j - 1]
            ) / (d * p[i][j - 1] + e)
        u[i][n - 1] = 1.0
        for j in range(n - 2, 0, -1):
            u[i][j] = p[i][j] * u[i][j + 1] + q[i][j]
    return u


@pytest.mark.parametrize("index", [0, 1], ids=["column", "row"])
def test_adi_sweeps_are_polybench(index: int):
    """Each generated sweep, solved, is PolyBench's own sweep of the same
    field on the interior lines: line i is column i of the column sweep's v,
    and row i of the row sweep's u."""
    n, tsteps = 30, 20
    field = np.random.default_rng(5).uniform(0.0, 2.0, size=(n, n))
    ops = polybench_adi.make(
        np.random.default_rng(5), index, (n - 2, n), N=n, TSTEPS=tsteps, B1=2.0, B2=1.0
    )
    x = polybench_adi.reference(*ops)
    if index == 0:
        want = polybench_column_sweep(field, tsteps).T[1:-1]
    else:
        want = polybench_row_sweep(field, tsteps)[1:-1]
    np.testing.assert_allclose(x, want, rtol=1e-9, atol=1e-9)


def test_rows_must_be_a_configured_size():
    config = {"name": "t", "sizes": [1000, 4000], "operands": {"kind": "diag_dominant"}}
    assert diag_dominant.shape(config, {"rows": 4000}) == (4000,)
    with pytest.raises(ValueError):
        diag_dominant.shape(config, {"rows": 3000})
