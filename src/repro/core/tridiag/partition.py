"""The parallel partition method for tridiagonal systems (paper §1, ref [1]).

Formulation (see package docstring): with blocks of m rows, the interface
unknowns are the *last* unknown of every block, s_p = x[(p+1)m - 1]. Each
block's (m-1)-row interior couples only to s_{p-1} (through its first row) and
s_p (through its last interior row), so one Thomas factorization per block with
three right-hand sides expresses the interior as

    x_interior = y - v * s_{p-1} - w * s_p                       (spikes)

Substituting the neighbouring interiors into each block's *last* row yields one
equation per block in (s_{p-1}, s_p, s_{p+1}) — the reduced tridiagonal system
of size P solved in Stage 2.

Stage 1 and Stage 3 are embarrassingly parallel over blocks — on the GPU of the
paper each CUDA stream takes a slice of blocks; here the block axis is the one
we shard/chunk (`chunked.py`, `repro.kernels.partition_stage1`).

The reduced system is the Schur complement of the matrix onto the interface
unknowns, so it keeps strict diagonal dominance (or symmetric positive
definiteness) and the method applies to it again:
:func:`partition_solve_recursive` partitions it until it is small enough for a
direct solve, which is how the fused Pallas path runs Stage 2 for reduced
systems too large for the Thomas kernel's VMEM tiles.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.tridiag.thomas import thomas, thomas_factor, thomas_solve_factored

Array = jax.Array


class PartitionCoeffs(NamedTuple):
    """Stage-1 output: per-block spike solutions + reduced-system rows."""

    y: Array  # (..., P, m-1) particular solution of interior
    v: Array  # (..., P, m-1) left spike  (coefficient of s_{p-1})
    w: Array  # (..., P, m-1) right spike (coefficient of s_p)
    red_dl: Array  # (..., P) reduced sub-diagonal
    red_d: Array  # (..., P) reduced diagonal
    red_du: Array  # (..., P) reduced super-diagonal
    red_b: Array  # (..., P) reduced RHS


def _blockify(a: Array, m: int) -> Array:
    *lead, n = a.shape
    assert n % m == 0, f"system size {n} not divisible by sub-system size {m}"
    return a.reshape(*lead, n // m, m)


def partition_stage1(
    dl: Array, d: Array, du: Array, b: Array, m: int
) -> PartitionCoeffs:
    """Parallel intra-block elimination (GPU Stage 1 in the paper)."""
    if m < 2:
        raise ValueError("sub-system size m must be >= 2")
    dlb, db, dub, bb = (_blockify(a, m) for a in (dl, d, du, b))
    # Interior rows are local indices 0..m-2 of each block.
    int_dl = dlb[..., :, : m - 1].at[..., :, 0].set(0.0)
    int_d = db[..., :, : m - 1]
    int_du = dub[..., :, : m - 1].at[..., :, m - 2].set(0.0)

    factors = thomas_factor(int_dl, int_d, int_du)
    # Three RHS: particular (d), left spike (a_first * e_0), right spike
    # (c_last_interior * e_{m-2}).
    rhs = jnp.stack(
        [
            bb[..., :, : m - 1],
            jnp.zeros_like(int_d).at[..., :, 0].set(dlb[..., :, 0]),
            jnp.zeros_like(int_d).at[..., :, m - 2].set(dub[..., :, m - 2]),
        ],
        axis=-1,
    )  # (..., P, m-1, 3)
    sol = thomas_solve_factored(factors, rhs)
    y, v, w = sol[..., 0], sol[..., 1], sol[..., 2]

    # Last row of each block: aL x[last_interior] + bL s_p + cL x_first_next = dL
    aL = dlb[..., :, m - 1]
    bL = db[..., :, m - 1]
    cL = dub[..., :, m - 1]  # 0 for the final block by convention
    dL = bb[..., :, m - 1]

    y_last, v_last, w_last = y[..., :, m - 2], v[..., :, m - 2], w[..., :, m - 2]
    # Next block's first interior row spikes (zero-padded past the last block).
    def pad(a):
        return jnp.concatenate(
            [a[..., 1:, 0], jnp.zeros_like(a[..., :1, 0])], axis=-1
        )
    y_nf, v_nf, w_nf = pad(y), pad(v), pad(w)

    red_dl = -aL * v_last
    red_d = bL - aL * w_last - cL * v_nf
    red_du = -cL * w_nf
    red_b = dL - aL * y_last - cL * y_nf
    return PartitionCoeffs(y, v, w, red_dl, red_d, red_du, red_b)


def partition_stage2(coeffs: PartitionCoeffs) -> Array:
    """Serial reduced solve of size P (CPU Stage 2 in the paper)."""
    return thomas(coeffs.red_dl, coeffs.red_d, coeffs.red_du, coeffs.red_b)


def partition_stage3(coeffs: PartitionCoeffs, s: Array) -> Array:
    """Parallel back-substitution: x_interior = y - v s_{p-1} - w s_p."""
    s_left = jnp.concatenate(
        [jnp.zeros_like(s[..., :1]), s[..., :-1]], axis=-1
    )
    x_int = (
        coeffs.y
        - coeffs.v * s_left[..., :, None]
        - coeffs.w * s[..., :, None]
    )
    x_blocks = jnp.concatenate([x_int, s[..., :, None]], axis=-1)
    *lead, p, m = x_blocks.shape
    return x_blocks.reshape(*lead, p * m)


def partition_solve(dl: Array, d: Array, du: Array, b: Array, m: int = 10) -> Array:
    """Full three-stage partition solve. Batched over leading dims of inputs."""
    coeffs = partition_stage1(dl, d, du, b, m)
    s = partition_stage2(coeffs)
    return partition_stage3(coeffs, s)


def partition_levels(p: int, m: int, fits: Callable[[int], bool]) -> int:
    """Levels :func:`partition_solve_recursive` takes on ``p`` rows: the
    fewest partitions, each taking P rows to ceil(P / m), that leave a system
    ``fits`` accepts (or a single row)."""
    if m < 2:
        raise ValueError("sub-system size m must be >= 2")
    levels = 0
    while p > 1 and not fits(p):
        p = -(-p // m)
        levels += 1
    return levels


def partition_solve_recursive(
    dl: Array,
    d: Array,
    du: Array,
    b: Array,
    *,
    m: int,
    stage1: Callable[..., PartitionCoeffs],
    stage3: Callable[[PartitionCoeffs, Array], Array],
    direct: Callable[..., Array],
    fits: Callable[[int], bool],
) -> Array:
    """Solve ``(..., P)`` tridiagonal systems, partitioning while too large.

    While ``fits(P)`` is false the system is padded to a multiple of ``m``
    with identity rows (``dl = 0, d = 1, du = 0, b = 0``: their unknowns are
    exactly 0 and couple to nothing), ``stage1`` reduces it to its ceil(P / m)
    interface rows, the reduced system is solved the same way, and ``stage3``
    back-substitutes; the padding is dropped. ``direct`` solves the system
    that fits. The stages are any pair with the signatures of
    :func:`partition_stage1` (``m`` bound) and :func:`partition_stage3`, so
    the same recursion runs on the jnp stages and on the Pallas kernels; the
    depth is :func:`partition_levels` of P.
    """
    p = d.shape[-1]
    if p <= 1 or fits(p):
        return direct(dl, d, du, b)
    extra = -p % m
    if extra:
        widths = [(0, 0)] * (d.ndim - 1) + [(0, extra)]
        dl, du, b = (jnp.pad(a, widths) for a in (dl, du, b))
        d = jnp.pad(d, widths, constant_values=1)
    c = stage1(dl, d, du, b)
    s = partition_solve_recursive(
        c.red_dl, c.red_d, c.red_du, c.red_b,
        m=m, stage1=stage1, stage3=stage3, direct=direct, fits=fits,
    )
    return stage3(c, s)[..., :p]
