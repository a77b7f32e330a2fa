"""PolyBench/C 4.2's ``adi``: one sweep of its Peaceman–Rachford ADI per
call, N − 2 tridiagonal systems of N points.

Reference LAPACK ``dgtsv``, control the Thomas algorithm one precision
step down (``bench/systems.py``). The algorithm reads four words a row
(dl, d, du, b) and writes one (x), in the configuration's dtype: 20 bytes a
row in fp32, whatever implements it.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from bench.systems import Operands, lowp_thomas, reference_solve

reference = reference_solve
control = lowp_thomas

#: A grid of 398 lines of 400 points: wide enough for the interleaved
#: layout, and stiff enough (mul1 = 640) that the bfloat16 control fails
#: by 10x.
TINY_GRID_N = 400


def shape(config: dict, traffic: dict) -> Tuple[int, ...]:
    """The configuration's grid: N − 2 interior lines of N points."""
    n = int(config["operands"]["N"])
    return (n - 2, n)


def make(
    rng: np.random.Generator,
    index: int,
    shape: Tuple[int, ...],
    N: int,
    TSTEPS: int,
    B1: float,
    B2: float,
) -> Operands:
    """One sweep of PolyBench/C 4.2's ``adi`` kernel (Peaceman–Rachford ADI
    for the 2-D heat equation on an N × N grid): even ``index`` gives its
    column sweep, odd its row sweep, each on a field uniform in [0, 2) from
    ``rng`` (the range of PolyBench's initial field (i + N − j)/N).

    The coefficients are PolyBench's: DX = DY = 1/N, DT = 1/TSTEPS,
    mul1 = B1·DT/DX², mul2 = B2·DT/DY², a = c = −mul1/2, b = 1 + mul1,
    d = f = −mul2/2, e = 1 + mul2. The column sweep solves, for each interior
    line i, a·v[j−1][i] + b·v[j][i] + c·v[j+1][i] =
    −d·u[j][i−1] + (1+2d)·u[j][i] − f·u[j][i+1]; the row sweep swaps the
    roles of (a, b, c) and (d, e, f) and of rows and columns. Each line is
    one system of all N points, its two boundary rows the identity
    equations of PolyBench's boundary value 1, so the interior solution is
    PolyBench's. ``shape`` is (N − 2 lines, N points)."""
    dt, dx2 = 1.0 / TSTEPS, (1.0 / N) ** 2
    mul1, mul2 = B1 * dt / dx2, B2 * dt / dx2
    a, b = -mul1 / 2.0, 1.0 + mul1
    d, e = -mul2 / 2.0, 1.0 + mul2
    field = rng.uniform(0.0, 2.0, size=(N, N))
    if index % 2 == 0:  # column sweep: g[i, j] = u[j][i]
        (lo, di), (ex_lo, ex_di), g = (a, b), (-d, 1.0 + 2.0 * d), field.T
    else:  # row sweep: g[i, j] = v[i][j]
        (lo, di), (ex_lo, ex_di), g = (d, e), (-a, 1.0 + 2.0 * a), field
    lines, n = shape
    rhs = np.ones((lines, n))
    rhs[:, 1:-1] = ex_lo * (g[:-2, 1:-1] + g[2:, 1:-1]) + ex_di * g[1:-1, 1:-1]
    dl = np.full(shape, lo)
    du = np.full(shape, lo)
    dg = np.full(shape, di)
    for diag in (dl, du):  # the boundary rows are x = 1
        diag[:, 0] = diag[:, -1] = 0.0
    dg[:, 0] = dg[:, -1] = 1.0
    return dl, dg, du, rhs


def least_bytes_per_row(config: dict) -> int:
    return 5 * np.dtype(config["dtype"]).itemsize


def tiny(config: dict, traffic: dict) -> Tuple[dict, dict]:
    return {**config, "operands": {**config["operands"], "N": TINY_GRID_N}}, traffic
