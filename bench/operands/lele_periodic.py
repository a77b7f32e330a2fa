"""Lele's sixth-order compact first derivative on a periodic grid: one
cyclic tridiagonal system per grid line, one chip's x-pencil of lines per
call (S. K. Lele, J. Comput. Phys. 103 (1992) 16-42, eq. 2.1.7).

    alpha f'[i-1] + f'[i] + alpha f'[i+1]
        = a (f[i+1] - f[i-1]) / (2h) + b (f[i+2] - f[i-2]) / (4h)

with alpha = 1/3, a = 14/9, b = 1/9, h = 2 pi / N and the indices taken
modulo N. The operands follow the solver's periodic convention: ``dl[0]``
multiplies ``x[N-1]`` and ``du[N-1]`` multiplies ``x[0]``. A call is
``solve_periodic_batched`` of every x-line of one chip's pencil.

Reference ``scipy.linalg.solve_circulant`` in float64 on the same operands:
the matrix is circulant, and this kind makes every line's coefficients
constant. Control: the cyclic Thomas algorithm with Sherman-Morrison, one
precision step down, its solves on the device. The algorithm reads four
words a row (dl, d, du, b) and writes one (x): 20 bytes a row in fp32,
whatever implements it.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import numpy as np

from bench.systems import Operands, lowp_thomas

#: A grid of 64 points a line on the configuration's pencil grid: 256
#: lines, wide enough for the interleaved layout, 8 blocks a line at m = 8.
TINY_N = 64


def lines(config: dict) -> int:
    """The x-lines of one chip's pencil: N/g0 * N/g1 for a g0 x g1 grid of
    pencils, as the configuration's ``lines`` states."""
    ops = config["operands"]
    n, (g0, g1) = int(ops["N"]), ops["pencil_grid"]
    count = (n // int(g0)) * (n // int(g1))
    if count != int(config["lines"]):
        raise ValueError(
            f"{config['name']}: lines {config['lines']} is not one pencil of "
            f"an {n}^3 grid on {g0} x {g1} chips ({count})"
        )
    return count


def shape(config: dict, traffic: dict) -> Tuple[int, ...]:
    """Every x-line of one pencil, N points each."""
    return (lines(config), int(config["operands"]["N"]))


def rhs(f: np.ndarray, a: float, b: float) -> np.ndarray:
    """The right-hand side of eq. 2.1.7 along the last axis, periodic."""
    n = f.shape[-1]
    h = 2.0 * np.pi / n

    def at(k: int) -> np.ndarray:  # f[i + k]
        return np.roll(f, -k, axis=-1)

    return a * (at(1) - at(-1)) / (2.0 * h) + b * (at(2) - at(-2)) / (4.0 * h)


def make(
    rng: np.random.Generator,
    index: int,
    shape: Tuple[int, ...],
    N: int,
    pencil_grid: Sequence[int],
    alpha: float,
    a: float,
    b: float,
) -> Operands:
    """The derivative of one pencil's field along x: the field uniform in
    [-1, 1) from ``rng``, so that every digit of the solve is exercised;
    ``dl = du = alpha`` everywhere, corners included, and ``d = 1``."""
    field = rng.uniform(-1.0, 1.0, size=shape)
    off = np.full(shape, float(alpha))
    return off, np.ones(shape), off.copy(), rhs(field, a, b)


def reference(dl, d, du, b) -> np.ndarray:
    """float64 solution of every line by ``scipy.linalg.solve_circulant``.
    Each line's coefficients must be constant along it."""
    from scipy.linalg import solve_circulant

    dl, d, du, b = (np.asarray(x, dtype=np.float64) for x in (dl, d, du, b))
    for name, coef in (("dl", dl), ("d", d), ("du", du)):
        if not np.all(coef == coef[..., :1]):
            raise ValueError(f"{name} varies along a line: the matrix is not circulant")
    c = np.zeros_like(b)
    c[..., 0] = d[..., 0]
    c[..., 1] += dl[..., 0]  # row i reads x[i-1] through dl
    c[..., -1] += du[..., 0]  # and x[i+1] through du
    return solve_circulant(c, b, caxis=-1, baxis=-1, outaxis=-1)


def control(dtype: str = "bfloat16") -> Callable:
    """The cyclic Thomas algorithm in ``dtype``, as a drop-in for the verb:
    the two Thomas solves of Sherman-Morrison (right-hand side and rank-one
    vector) on the device in ``dtype``, and the correction rounded to it."""
    import jax.numpy as jnp

    thomas = lowp_thomas(dtype)
    low = jnp.dtype(dtype)

    def verb(dl, d, du, b):
        dl, d, du, b = (np.asarray(x, dtype=np.float32).astype(low) for x in (dl, d, du, b))
        gamma = -d[..., :1]
        ratio = dl[..., :1] / gamma
        d_mod = d.copy()
        d_mod[..., :1] = d[..., :1] - gamma
        d_mod[..., -1:] = d[..., -1:] - du[..., -1:] * ratio
        u = np.zeros_like(b)
        u[..., :1] = gamma
        u[..., -1:] = du[..., -1:]
        y = thomas(dl, d_mod, du, b).astype(low)
        z = thomas(dl, d_mod, du, u).astype(low)
        beta = (y[..., :1] + ratio * y[..., -1:]) / (
            np.ones_like(gamma) + z[..., :1] + ratio * z[..., -1:]
        )
        return (y - beta * z).astype(np.float32)

    return verb


def least_bytes_per_row(config: dict) -> int:
    return 5 * np.dtype(config["dtype"]).itemsize


def tiny(config: dict, traffic: dict) -> Tuple[dict, dict]:
    ops = {**config["operands"], "N": TINY_N}
    g0, g1 = ops["pencil_grid"]
    count = (TINY_N // int(g0)) * (TINY_N // int(g1))
    return {**config, "operands": ops, "lines": count}, traffic
