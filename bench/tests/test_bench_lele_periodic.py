"""The ``lele_periodic`` operand kind: its right-hand side is Lele's
eq. 2.1.7, its circulant reference agrees with a dense solve, its pool
follows the seed, and its cell runs through the harness at a small size,
where the non-periodic solve of the same operands fails the limit."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import systems
from bench.harness import Run, load_peaks, run_cell
from bench.trace import Reduction
from bench.manifest import Manifest

from .conftest import REPO

MANIFEST = Manifest.load(REPO)
CELL = "compact6_tgv512.x_pencil"
CONFIG = MANIFEST.config("compact6_tgv512")
PARAMS = {k: v for k, v in CONFIG["operands"].items() if k != "kind"}
kind = MANIFEST.operands("lele_periodic")
SEED = 2**31 + 977


def cyclic_matrix(dl, d, du) -> np.ndarray:
    """The dense matrix of one cyclic line: row i reads x[i-1], x[i],
    x[i+1], indices modulo n."""
    n = d.shape[-1]
    a = np.diag(d).astype(np.float64)
    for i in range(n):
        a[i, (i - 1) % n] += dl[i]
        a[i, (i + 1) % n] += du[i]
    return a


def lele_derivative(f: np.ndarray) -> np.ndarray:
    """f' of periodic samples by the scheme, solved densely in float64."""
    n = f.shape[-1]
    off = np.full(n, PARAMS["alpha"])
    b = kind.rhs(f, PARAMS["a"], PARAMS["b"])
    return np.linalg.solve(cyclic_matrix(off, np.ones(n), off), b)


def test_rhs_is_eq_2_1_7():
    f = np.random.default_rng(3).uniform(-1, 1, 40)
    n, h = f.size, 2 * np.pi / f.size
    a, b = PARAMS["a"], PARAMS["b"]
    got = kind.rhs(f, a, b)
    for i in (0, 1, 17, n - 2, n - 1):
        want = a * (f[(i + 1) % n] - f[i - 1]) / (2 * h) + b * (
            f[(i + 2) % n] - f[i - 2]
        ) / (4 * h)
        assert got[i] == pytest.approx(want, rel=1e-14)


def test_the_scheme_is_sixth_order_on_a_sine():
    """sin(kx) differentiates to k cos(kx); halving h divides the error by
    about 2**6 = 64."""
    k = 3
    errs = []
    for n in (32, 64):
        x = 2 * np.pi * np.arange(n) / n
        errs.append(np.max(np.abs(lele_derivative(np.sin(k * x)) - k * np.cos(k * x))))
    assert errs[1] < 1e-6
    assert 50 < errs[0] / errs[1] < 80


def test_reference_matches_a_dense_solve():
    ops = kind.make(systems.pool_rng(SEED, 0), 0, (5, 48), **PARAMS)
    x = kind.reference(*ops)
    for k in range(5):
        a = cyclic_matrix(*(o[k] for o in ops[:3]))
        np.testing.assert_allclose(x[k], np.linalg.solve(a, ops[3][k]), rtol=1e-12, atol=1e-12)


def test_reference_refuses_a_line_that_is_not_circulant():
    dl, d, du, b = kind.make(systems.pool_rng(SEED, 0), 0, (2, 16), **PARAMS)
    d[1, 3] = 2.0
    with pytest.raises(ValueError, match="not circulant"):
        kind.reference(dl, d, du, b)


def test_pool_is_seeded():
    config, traffic = kind.tiny(CONFIG, {"pool": 4})
    first = systems.make_pool(kind, config, traffic, SEED)
    again = systems.make_pool(kind, config, traffic, SEED)
    other = systems.make_pool(kind, config, traffic, SEED + 1)
    assert len(first) == 4
    for a, b in zip(first, again):
        for x, y in zip(a, b):
            assert x.dtype == np.float32 and np.array_equal(x, y)
    assert not np.array_equal(first[0][3], other[0][3])
    assert not np.array_equal(first[0][3], first[1][3])  # each entry its own field


def test_the_file_states_one_pencil():
    assert kind.shape(CONFIG, {}) == (16384, 512)
    with pytest.raises(ValueError, match="pencil"):
        kind.shape({**CONFIG, "lines": 262144}, {})


def run(root: Path, verb_for=None) -> dict:
    return run_cell(
        Manifest.load(root), CELL, SEED, 0.3, False, 0.0, load_peaks("TPU v5 lite"),
        verb_for=verb_for,
    )


def test_the_cell_runs_at_its_tiny_size(tiny_root: Path):
    config = Manifest.load(tiny_root).config("compact6_tgv512")
    assert kind.shape(config, {}) == (256, kind.TINY_N)  # >= 32 lines: interleaved
    res = run(tiny_root)
    assert res["correct"], res["check"]


def test_the_missing_wrap_fails_the_limit(tiny_root: Path):
    """The same operands solved as non-periodic systems (their corners
    dropped) are not correct, by far."""
    res = run(tiny_root, verb_for=lambda s, name: s.solve_batched)
    assert res["correct"] is False
    err = res["check"]["max_rel_err"]
    assert err["value"] > 100 * err["limit"]


def test_float32_control_stands_in_correctly(tiny_root: Path):
    """The control's algorithm at the configuration's precision passes:
    what fails the bfloat16 control is the precision, not the code."""
    control = kind.control("float32")
    res = run(tiny_root, verb_for=lambda s, name: control)
    assert res["correct"], res["check"]


def reduction(op_ns: dict) -> Reduction:
    return Reduction(
        calls=4, window_ns=10**9, busy_ns=4e7, class_ns={}, op_ns=op_ns,
        call_ns=[], call_busy_ns=[], idle_gaps={},
    )


@pytest.mark.parametrize(
    "op_ns, ms",
    [
        ({"_periodic_correction.1": 80_000.0, "copy.3": 5e6}, 0.02),  # 4 calls of 20 us
        ({"_thomas_impl_wide.1": 8e5, "copy.3": 5e6}, None),
    ],
    ids=["kernel ran", "no periodic op"],
)
def test_periodic_ms_reads_the_correction_kernel(op_ns, ms):
    run = Run(
        cell=CELL, config=CONFIG, traffic={}, peaks=load_peaks("TPU v5 lite"),
        shape=kind.shape(CONFIG, {}), reduction=reduction(op_ns),
    )
    got = MANIFEST.reader("periodic_ms").read(run)
    assert got == (None if ms is None else pytest.approx(ms))
