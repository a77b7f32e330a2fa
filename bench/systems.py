"""What the operand kinds share: the seeded pool, the tridiagonal fp64
reference, the error measure and the low-precision control.

Nothing here imports the solver under test. Each kind
(``bench/operands/<kind>.py``) makes its own operands; the tridiagonal
kinds take the reference here, LAPACK's ``dgtsv`` (Gaussian elimination
with partial pivoting) in float64 on the very fp32 operands the solver was
given, and the control here, the Thomas algorithm computed on the device in
the precision step below the configurations' fp32.

Diagonal convention (the solver's): ``dl[i]`` multiplies ``x[i-1]`` and
``du[i]`` multiplies ``x[i+1]``; ``dl[..., 0]`` and ``du[..., -1]`` are 0.
"""

from __future__ import annotations

from types import ModuleType
from typing import Callable, List, Tuple

import numpy as np

Operands = Tuple[np.ndarray, ...]


def pool_rng(seed: int, index: int) -> np.random.Generator:
    """The generator for pool entry ``index`` of a run seeded ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(index)]))


def make_pool(
    kind: ModuleType, config: dict, traffic: dict, seed: int
) -> List[Operands]:
    """The mix's ``pool`` operand sets for the run seeded ``seed``, in the
    configuration's dtype. ``kind`` is the module the configuration's
    ``operands.kind`` names; the entry's other keys are the parameters of
    its ``make``."""
    params = {k: v for k, v in config["operands"].items() if k != "kind"}
    shape = kind.shape(config, traffic)
    return [
        tuple(
            np.ascontiguousarray(a, dtype=config["dtype"])
            for a in kind.make(pool_rng(seed, i), i, shape, **params)
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
