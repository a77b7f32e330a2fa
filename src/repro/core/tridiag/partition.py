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

A *periodic* (cyclic) system closes on itself: ``dl[0]`` couples row 0 to
``x[n-1]`` and ``du[n-1]`` couples row n-1 to ``x[0]``. The same blocks then
wrap around: block 0's left spike couples to s_{P-1}, and the last block's
last row to block 0's first interior row. With ``periodic=True`` both
neighbour shifts roll along the block axis instead of filling with zeros, and
the reduced system is cyclic tridiagonal of size P, with corners
``red_dl[0]`` and ``red_du[P-1]``; :func:`cyclic_solve` solves it with any
solver of plain tridiagonal systems (Sherman–Morrison).

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

from repro.core.tridiag import spans
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


def next_block(a: Array, *, axis: int, periodic: bool) -> Array:
    """``a`` shifted one block back along the block ``axis``: entry p holds
    block p+1's. Past the last block it is zero, or block 0's when
    ``periodic`` (the wrap-around)."""
    if periodic:
        return jnp.roll(a, -1, axis=axis)
    head = jax.lax.slice_in_dim(a, 1, a.shape[axis], axis=axis)
    zero = jnp.zeros_like(jax.lax.slice_in_dim(a, 0, 1, axis=axis))
    return jnp.concatenate([head, zero], axis=axis)


def prev_block(a: Array, *, axis: int, periodic: bool) -> Array:
    """``a`` shifted one block on along ``axis``: entry p holds block p-1's.
    Before block 0 it is zero, or the last block's when ``periodic``."""
    if periodic:
        return jnp.roll(a, 1, axis=axis)
    tail = jax.lax.slice_in_dim(a, 0, a.shape[axis] - 1, axis=axis)
    zero = jnp.zeros_like(jax.lax.slice_in_dim(a, 0, 1, axis=axis))
    return jnp.concatenate([zero, tail], axis=axis)


def partition_stage1(
    dl: Array, d: Array, du: Array, b: Array, m: int, periodic: bool = False
) -> PartitionCoeffs:
    """Parallel intra-block elimination (GPU Stage 1 in the paper).

    ``periodic`` wraps the last block's right neighbour round to block 0."""
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
    cL = dub[..., :, m - 1]  # the final block's is 0 unless periodic
    dL = bb[..., :, m - 1]

    y_last, v_last, w_last = y[..., :, m - 2], v[..., :, m - 2], w[..., :, m - 2]
    # Next block's first interior row spikes (zero past the last block, or
    # block 0's when periodic).
    y_nf, v_nf, w_nf = (
        next_block(a[..., 0], axis=-1, periodic=periodic) for a in (y, v, w)
    )

    red_dl = -aL * v_last
    red_d = bL - aL * w_last - cL * v_nf
    red_du = -cL * w_nf
    red_b = dL - aL * y_last - cL * y_nf
    return PartitionCoeffs(y, v, w, red_dl, red_d, red_du, red_b)


def partition_stage2(coeffs: PartitionCoeffs) -> Array:
    """Serial reduced solve of size P (CPU Stage 2 in the paper)."""
    return thomas(coeffs.red_dl, coeffs.red_d, coeffs.red_du, coeffs.red_b)


def partition_stage3(coeffs: PartitionCoeffs, s: Array, periodic: bool = False) -> Array:
    """Parallel back-substitution: x_interior = y - v s_{p-1} - w s_p, with
    s_{-1} = s_{P-1} when ``periodic`` (else 0)."""
    s_left = prev_block(s, axis=-1, periodic=periodic)
    x_int = (
        coeffs.y
        - coeffs.v * s_left[..., :, None]
        - coeffs.w * s[..., :, None]
    )
    x_blocks = jnp.concatenate([x_int, s[..., :, None]], axis=-1)
    *lead, p, m = x_blocks.shape
    return x_blocks.reshape(*lead, p * m)


def partition_solve(
    dl: Array, d: Array, du: Array, b: Array, m: int = 10, periodic: bool = False
) -> Array:
    """Full three-stage partition solve. Batched over leading dims of inputs.

    ``periodic`` solves cyclic systems (``dl[..., 0]`` multiplies
    ``x[..., n-1]``, ``du[..., n-1]`` multiplies ``x[..., 0]``)."""
    coeffs = partition_stage1(dl, d, du, b, m, periodic)
    if periodic:
        s = cyclic_solve(
            thomas, coeffs.red_dl, coeffs.red_d, coeffs.red_du, coeffs.red_b
        )
    else:
        s = partition_stage2(coeffs)
    return partition_stage3(coeffs, s, periodic)


def rank_one_update(y: Array, z: Array, beta: Array, axis: int = 0) -> Array:
    """``y - beta * z``: the Sherman–Morrison correction of :func:`cyclic_solve`
    (``beta`` has length 1 along the solve ``axis``)."""
    return y - beta * z


def cyclic_solve(
    solve: Callable[..., Array],
    dl: Array,
    d: Array,
    du: Array,
    b: Array,
    *,
    axis: int = -1,
    update: Callable[[Array, Array, Array, int], Array] = rank_one_update,
) -> Array:
    """Solve cyclic tridiagonal systems along ``axis`` with ``solve``, a
    solver of plain tridiagonal systems laid out the same way.

    Row 0's ``dl`` multiplies the last unknown and the last row's ``du`` the
    first. Operands are ``(P,)``, or 2-D with the systems along the other
    axis: ``(B, P)`` with ``axis=-1``, ``(P, B)`` with ``axis=0``.

    Sherman–Morrison, as in the cyclic Thomas algorithm: A = A' + u vᵀ with
    γ = -d₀, u = (γ, 0, …, 0, c) and v = (1, 0, …, 0, a/γ) for the corners
    a = dl₀ and c = du_{P-1}, so A' is A with d₀ - γ and d_{P-1} - a c / γ on
    its diagonal and no corners. ``solve`` takes A' [y z] = [b u] in one call,
    the two right-hand sides stacked along the systems' axis, and
    ``update(y, z, β, axis)`` returns x = y - β z with β = vᵀy / (1 + vᵀz).
    A' is plain tridiagonal, so ``solve`` may pad it with identity rows or
    partition it again. What the correction adds around ``solve`` (A', u,
    the stacking, β and the update) runs under the device scope
    ``tridiag/periodic``.

    With P ≤ 2 both neighbours of a row are the same unknown: the corners
    fold into the off-diagonals and ``solve`` takes the system alone.
    """
    if d.ndim == 1:
        return cyclic_solve(
            solve, dl[None], d[None], du[None], b[None], update=update
        )[0]
    if d.ndim != 2:
        raise ValueError(f"cyclic_solve takes (P,) or 2-D operands, got {d.shape}")
    axis = axis % 2
    lanes = 1 - axis
    p = d.shape[axis]

    def row(a: Array, i: int) -> Array:  # row i along the solve axis, kept 2-D
        return jax.lax.slice_in_dim(a, i, i + 1, axis=axis)

    def put(a: Array, i: int, value: Array) -> Array:
        return jax.lax.dynamic_update_slice_in_dim(a, value, i, axis=axis)

    a0, c_last = row(dl, 0), row(du, p - 1)
    if p <= 2:
        # s_{-1} and s_{+1} are one unknown: fold each corner onto it.
        if p == 1:
            return solve(
                jnp.zeros_like(dl), d + dl + du, jnp.zeros_like(du), b
            )
        dl = put(dl, 0, jnp.zeros_like(a0))
        dl = put(dl, 1, row(dl, 1) + c_last)
        du = put(du, 1, jnp.zeros_like(c_last))
        du = put(du, 0, row(du, 0) + a0)
        return solve(dl, d, du, b)
    with jax.named_scope(spans.PERIODIC):
        d0, d_last = row(d, 0), row(d, p - 1)
        gamma = -d0
        ratio = a0 / gamma
        d_mod = put(put(d, 0, d0 - gamma), p - 1, d_last - c_last * ratio)
        dl_mod = put(dl, 0, jnp.zeros_like(a0))
        du_mod = put(du, p - 1, jnp.zeros_like(c_last))
        u = put(put(jnp.zeros_like(b), 0, gamma), p - 1, c_last)

        def both(first: Array, second: Array) -> Array:
            return jnp.concatenate([first, second], axis=lanes)

        stacked = [both(dl_mod, dl_mod), both(d_mod, d_mod), both(du_mod, du_mod)]
        stacked.append(both(b, u))
    yz = solve(*stacked)  # one launch for both right-hand sides
    with jax.named_scope(spans.PERIODIC):
        k = b.shape[lanes]
        y = jax.lax.slice_in_dim(yz, 0, k, axis=lanes)
        z = jax.lax.slice_in_dim(yz, k, 2 * k, axis=lanes)
        beta = (row(y, 0) + ratio * row(y, p - 1)) / (
            1 + row(z, 0) + ratio * row(z, p - 1)
        )
        return update(y, z, beta, axis)


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
