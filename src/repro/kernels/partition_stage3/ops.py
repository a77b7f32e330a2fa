"""Jitted wrapper for the Stage-3 Pallas kernel, and the full Pallas solve.

``periodic`` (static) back-substitutes cyclic systems: block 0's left
interface value is the last block's (``s_left`` rolls along the block axis)
instead of zero. Non-periodic calls trace exactly as without the flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.tridiag.partition import PartitionCoeffs
from repro.core.tridiag.thomas import thomas
from repro.kernels import common
from repro.kernels.partition_stage3.stage3 import (
    stage3_tiled,
    stage3_tiled_batched,
    stage3_tiled_wide,
)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret", "periodic"))
def _stage3_impl(y, v, w, s, *, block_p: int, interpret: bool, periodic: bool = False):
    p, mi = y.shape
    m = mi + 1
    pp = common.round_up(p, block_p)
    def padT(a):
        return common.pad_axis_to(a.T, pp, axis=1)

    if periodic:
        s_left = jnp.roll(s, 1)
    else:
        s_left = jnp.concatenate([jnp.zeros_like(s[:1]), s[:-1]])
    xT = stage3_tiled(
        padT(y), padT(v), padT(w),
        common.pad_axis_to(s[None, :], pp, axis=1),
        common.pad_axis_to(s_left[None, :], pp, axis=1),
        m=m, block_p=block_p, interpret=interpret,
    )
    return xT[:, :p].T.reshape(p * m)


def partition_stage3_pallas(
    coeffs: PartitionCoeffs,
    s: jax.Array,
    *,
    block_p: int = 512,
    interpret: bool | None = None,
    periodic: bool = False,
) -> jax.Array:
    """Back-substitute interface values into block interiors via Pallas
    (of a cyclic system with ``periodic``)."""
    if interpret is None:
        interpret = common.interpret_default()
    p = s.shape[-1]
    block_p = min(block_p, common.round_up(p, common.LANES))
    return _stage3_impl(
        coeffs.y, coeffs.v, coeffs.w, s,
        block_p=block_p, interpret=interpret, periodic=periodic,
    )


def partition_solve_pallas(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    m: int = 10,
    interpret: bool | None = None,
) -> jax.Array:
    """Full partition solve with Pallas Stage-1/Stage-3 and jnp Stage 2."""
    from repro.kernels.partition_stage1.ops import partition_stage1_pallas

    coeffs = partition_stage1_pallas(dl, d, du, b, m=m, interpret=interpret)
    s = thomas(coeffs.red_dl, coeffs.red_d, coeffs.red_du, coeffs.red_b)
    return partition_stage3_pallas(coeffs, s, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_b", "interpret", "periodic")
)
def _stage3_impl_wide(
    y, v, w, s, *, block_rows: int, block_b: int, interpret: bool,
    periodic: bool = False,
):
    p, mi, bsz = y.shape
    m = mi + 1
    pr = common.round_up(p, block_rows)
    bp = common.round_up(bsz, block_b)
    # s_left shifts along the block axis; row 0 is every system's first block.
    if periodic:
        s_left = jnp.roll(s, 1, axis=0)
    else:
        s_left = jnp.concatenate([jnp.zeros_like(s[:1]), s[:-1]], axis=0)
    def pad3(a):
        return common.pad_axis_to(common.pad_axis_to(a, bp, axis=2), pr, axis=0)

    xw = stage3_tiled_wide(
        pad3(y), pad3(v), pad3(w),
        pad3(s[:, None, :]), pad3(s_left[:, None, :]),
        m=m, block_rows=block_rows, block_b=block_b, interpret=interpret,
    )
    return xw[:p, :, :bsz]


def partition_stage3_pallas_wide(
    coeffs: PartitionCoeffs,
    s: jax.Array,
    *,
    block_rows: int = 32,
    block_b: int = 256,
    interpret: bool | None = None,
    periodic: bool = False,
) -> jax.Array:
    """Back-substitution on batch-interleaved coeffs: (P, m-1, B) spikes +
    (P, B) interface values → (P, m, B) wide solution (cyclic systems with
    ``periodic``)."""
    if interpret is None:
        interpret = common.interpret_default()
    p, _, bsz = coeffs.y.shape
    block_b = min(block_b, common.round_up(bsz, common.LANES))
    block_rows = min(block_rows, common.round_up(p, common.SUBLANES))
    return _stage3_impl_wide(
        coeffs.y, coeffs.v, coeffs.w, s,
        block_rows=block_rows, block_b=block_b, interpret=interpret,
        periodic=periodic,
    )


@functools.partial(jax.jit, static_argnames=("block_p", "interpret", "periodic"))
def _stage3_impl_batched(
    y, v, w, s, *, block_p: int, interpret: bool, periodic: bool = False
):
    bsz, p, mi = y.shape
    m = mi + 1
    pp = common.round_up(p, block_p)
    def padT(a):
        return common.pad_axis_to(a.transpose(0, 2, 1), pp, axis=2)

    if periodic:
        s_left = jnp.roll(s, 1, axis=1)
    else:
        s_left = jnp.concatenate([jnp.zeros_like(s[:, :1]), s[:, :-1]], axis=1)
    xT = stage3_tiled_batched(
        padT(y), padT(v), padT(w),
        common.pad_axis_to(s[:, None, :], pp, axis=2),
        common.pad_axis_to(s_left[:, None, :], pp, axis=2),
        m=m, block_p=block_p, interpret=interpret,
    )
    return xT[:, :, :p].transpose(0, 2, 1).reshape(bsz, p * m)


def partition_stage3_pallas_batched(
    coeffs: PartitionCoeffs,
    s: jax.Array,
    *,
    block_p: int = 512,
    interpret: bool | None = None,
    periodic: bool = False,
) -> jax.Array:
    """Batched-grid back-substitution for (B, P, m-1) spikes and (B, P) s
    (cyclic systems with ``periodic``)."""
    if interpret is None:
        interpret = common.interpret_default()
    p = s.shape[-1]
    block_p = min(block_p, common.round_up(p, common.LANES))
    return _stage3_impl_batched(
        coeffs.y, coeffs.v, coeffs.w, s,
        block_p=block_p, interpret=interpret, periodic=periodic,
    )


def partition_solve_pallas_batched(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    m: int = 10,
    interpret: bool | None = None,
) -> jax.Array:
    """Full batched (B, N) partition solve: batched-grid Pallas Stage 1 and
    Stage 3 with a batch-vectorized jnp Thomas on the B reduced systems."""
    from repro.kernels.partition_stage1.ops import partition_stage1_pallas_batched

    coeffs = partition_stage1_pallas_batched(dl, d, du, b, m=m, interpret=interpret)
    s = thomas(coeffs.red_dl, coeffs.red_d, coeffs.red_du, coeffs.red_b)
    return partition_stage3_pallas_batched(coeffs, s, interpret=interpret)
