"""Operands made from the seed, the fp64 reference and the low-precision control.

Nothing here imports the solver under test. The generators follow the
paper's setting (random strictly diagonally dominant systems) and the
sweeps of PolyBench/C's ``adi`` kernel; the reference is LAPACK's ``dgtsv``
(Gaussian elimination with partial pivoting) in float64 on the very fp32
operands the solver was given; the control is the Thomas algorithm computed
in bfloat16 on the device, the precision step below the configurations'
fp32.

Diagonal convention (the solver's): ``dl[i]`` multiplies ``x[i-1]`` and
``du[i]`` multiplies ``x[i+1]``; ``dl[..., 0]`` and ``du[..., -1]`` are 0.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

Operands = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def pool_rng(seed: int, index: int) -> np.random.Generator:
    """The generator for pool entry ``index`` of a run seeded ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(index)]))


def diag_dominant(
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


def polybench_adi(
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


def diag_dominant_shape(config: dict, traffic: dict) -> Tuple[int, ...]:
    """One system of the mix's ``rows``, which must be one of the
    configuration's ``sizes``."""
    rows = int(traffic["rows"])
    if rows not in config["sizes"]:
        raise ValueError(f"rows {rows} is not one of {config['name']}'s sizes")
    return (rows,)


def polybench_adi_shape(config: dict, traffic: dict) -> Tuple[int, ...]:
    """The configuration's grid: N − 2 interior lines of N points."""
    n = int(config["operands"]["N"])
    return (n - 2, n)


#: kind -> (the call's operand shape from configuration and mix, generator)
GENERATORS: Dict[str, Tuple[Callable[..., Tuple[int, ...]], Callable[..., Operands]]] = {
    "diag_dominant": (diag_dominant_shape, diag_dominant),
    "polybench_adi": (polybench_adi_shape, polybench_adi),
}


def call_shape(config: dict, traffic: dict) -> Tuple[int, ...]:
    """The operand shape of every call of ``traffic`` on ``config``. It is
    read from one place: the configuration or the mix, as the kind says."""
    return GENERATORS[config["operands"]["kind"]][0](config, traffic)


def make_pool(config: dict, traffic: dict, seed: int) -> List[Operands]:
    """The mix's ``pool`` operand sets for the run seeded ``seed``, in the
    configuration's dtype. The configuration's ``operands`` entry names a
    generator by ``kind``; its other keys are the generator's parameters."""
    operands = config["operands"]
    params = {k: v for k, v in operands.items() if k != "kind"}
    gen = GENERATORS[operands["kind"]][1]
    shape = call_shape(config, traffic)
    return [
        tuple(
            np.ascontiguousarray(a, dtype=config["dtype"])
            for a in gen(pool_rng(seed, i), i, shape, **params)
        )
        for i in range(int(traffic["pool"]))
    ]


# ------------------------------------------------------------- reference --
def reference_solve(dl, d, du, b) -> np.ndarray:
    """float64 solution of every system in the operands (leading dims are
    a batch), by LAPACK ``dgtsv``."""
    from scipy.linalg import lapack

    ops = [np.asarray(a, dtype=np.float64) for a in (dl, d, du, b)]
    n = ops[1].shape[-1]
    flat = [a.reshape(-1, n) for a in ops]
    x = np.empty_like(flat[3])
    for k in range(flat[1].shape[0]):
        dl_k, d_k, du_k, b_k = (a[k] for a in flat)
        *_, xk, info = lapack.dgtsv(dl_k[1:], d_k, du_k[:-1], b_k[:, None])
        if info != 0:
            raise ArithmeticError(f"dgtsv failed on system {k}: info={info}")
        x[k] = xk[:, 0]
    return x.reshape(ops[3].shape)


def max_rel_err(x, ref) -> float:
    """Worst normwise relative error over the systems (last axis = rows):
    max over systems of max|x − ref| / max|ref|. NaN or inf reads as inf."""
    x = np.asarray(x, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if x.shape != ref.shape:
        return float("inf")
    num = np.max(np.abs(x - ref), axis=-1)
    den = np.max(np.abs(ref), axis=-1)
    err = np.max(num / den)
    return float(err) if np.isfinite(err) else float("inf")


# --------------------------------------------------------------- control --
def lowp_thomas(dtype: str = "bfloat16") -> Callable:
    """The Thomas algorithm in ``dtype`` on the default device, as a
    drop-in for a solver verb: takes host operands ``(…, n)`` and returns
    the host solution in float32. Every operation rounds to ``dtype``."""
    import jax
    import jax.numpy as jnp

    lowp = jnp.dtype(dtype)

    @jax.jit
    def solve(dl, d, du, b):
        rows = [jnp.moveaxis(a.astype(lowp), -1, 0) for a in (dl, d, du, b)]

        def fwd(carry, row):
            dh_prev, bh_prev = carry
            dl_i, d_i, du_prev, b_i = row
            w = dl_i / dh_prev
            dh = d_i - w * du_prev
            bh = b_i - w * bh_prev
            return (dh, bh), (dh, bh)

        dl_r, d_r, du_r, b_r = rows
        du_prev = jnp.concatenate([jnp.zeros_like(du_r[:1]), du_r[:-1]])
        first = (d_r[0], b_r[0])
        _, (dh, bh) = jax.lax.scan(fwd, first, (dl_r[1:], d_r[1:], du_prev[1:], b_r[1:]))
        dh = jnp.concatenate([d_r[:1], dh])
        bh = jnp.concatenate([b_r[:1], bh])

        def bwd(x_next, row):
            dh_i, bh_i, du_i = row
            x = (bh_i - du_i * x_next) / dh_i
            return x, x

        x_last = bh[-1] / dh[-1]
        _, xs = jax.lax.scan(bwd, x_last, (dh[:-1], bh[:-1], du_r[:-1]), reverse=True)
        x = jnp.concatenate([xs, x_last[None]])
        return jnp.moveaxis(x, 0, -1).astype(jnp.float32)

    def verb(dl, d, du, b):
        return np.asarray(solve(dl, d, du, b))

    return verb
