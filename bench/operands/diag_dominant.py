"""The paper's setting: one random strictly diagonally dominant
tridiagonal system per call, of one of the configuration's ``sizes``.

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

#: One of table4's sizes, small enough for a CPU run of about a second.
TINY_ROWS = 10000


def shape(config: dict, traffic: dict) -> Tuple[int, ...]:
    """One system of the mix's ``rows``, which must be one of the
    configuration's ``sizes``."""
    rows = int(traffic["rows"])
    if rows not in config["sizes"]:
        raise ValueError(f"rows {rows} is not one of {config['name']}'s sizes")
    return (rows,)


def make(
    rng: np.random.Generator, index: int, shape: Tuple[int, ...], dominance: float
) -> Operands:
    """Random strictly diagonally dominant system in fp64 (the paper's
    setting): off-diagonals uniform in [-1, 1], |d| = dominance·(|dl|+|du|)
    plus uniform [0.5, 1.5], random sign, b = A @ x for a standard-normal x."""
    n = shape[-1]
    dl = rng.uniform(-1.0, 1.0, size=shape)
    du = rng.uniform(-1.0, 1.0, size=shape)
    dl[..., 0] = 0.0
    du[..., n - 1] = 0.0
    mag = np.abs(dl) + np.abs(du)
    sign = np.where(rng.uniform(size=shape) < 0.5, -1.0, 1.0)
    d = sign * (mag * dominance + rng.uniform(0.5, 1.5, size=shape))
    x = rng.standard_normal(shape)
    b = d * x
    b[..., 1:] += dl[..., 1:] * x[..., :-1]
    b[..., :-1] += du[..., :-1] * x[..., 1:]
    return dl, d, du, b


def least_bytes_per_row(config: dict) -> int:
    return 5 * np.dtype(config["dtype"]).itemsize


def tiny(config: dict, traffic: dict) -> Tuple[dict, dict]:
    return config, {**traffic, "rows": TINY_ROWS}
