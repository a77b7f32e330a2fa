"""Partition-method Stage 1 as a Pallas TPU kernel (the paper's hot kernel).

Each grid step owns ``block_p`` blocks of the partitioned system, laid out
transposed: tiles of shape (m, block_p) with the m in-block rows on sublanes
and the blocks on lanes. One fused pass computes the three spike solutions

    y = B⁻¹ b_int,  v = B⁻¹ (a_first e_0),  w = B⁻¹ (c_last e_{m-2})

sharing a single interior factorization (the w-spike forward sweep is free:
its forward image is du[m-2] e_{m-2}). The reduced interface rows are
assembled outside the kernel (cheap elementwise shifts — see ops.py).

The grid over blocks is the stream analogue: on TPU, Pallas double-buffers the
HBM→VMEM DMA of tile i+1 behind the recurrence of tile i; the paper tunes how
many such slices are in flight (DESIGN.md §2.1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _stage1_kernel(dl_ref, d_ref, du_ref, b_ref, y_ref, v_ref, w_ref, dhat_ref, *, m: int):
    mi = m - 1  # interior size
    bb = y_ref.shape[1]
    dtype = y_ref.dtype

    # Forward elimination (shared factorization; spikes seeded per their RHS).
    dhat_ref[0:1, :] = d_ref[0:1, :]
    y_ref[0:1, :] = b_ref[0:1, :]
    v_ref[0:1, :] = dl_ref[0:1, :]
    w_ref[...] = jnp.zeros((mi, bb), dtype)

    def fwd(i, carry):
        wgt = dl_ref[pl.ds(i, 1), :] / dhat_ref[pl.ds(i - 1, 1), :]
        dhat_ref[pl.ds(i, 1), :] = (
            d_ref[pl.ds(i, 1), :] - wgt * du_ref[pl.ds(i - 1, 1), :]
        )
        y_ref[pl.ds(i, 1), :] = b_ref[pl.ds(i, 1), :] - wgt * y_ref[pl.ds(i - 1, 1), :]
        v_ref[pl.ds(i, 1), :] = -wgt * v_ref[pl.ds(i - 1, 1), :]
        return carry

    common.fori_loop(1, mi, fwd)

    # Backward substitution, all three spikes per step (in place).
    last = mi - 1
    dhat_last = dhat_ref[pl.ds(last, 1), :]
    y_ref[pl.ds(last, 1), :] = y_ref[pl.ds(last, 1), :] / dhat_last
    v_ref[pl.ds(last, 1), :] = v_ref[pl.ds(last, 1), :] / dhat_last
    # w-spike forward image is du[m-2]·e_last, so its backward seed is direct:
    w_ref[pl.ds(last, 1), :] = du_ref[pl.ds(last, 1), :] / dhat_last

    def bwd(j, carry):
        i = last - 1 - j
        du_i = du_ref[pl.ds(i, 1), :]
        dhat_i = dhat_ref[pl.ds(i, 1), :]
        y_ref[pl.ds(i, 1), :] = (
            y_ref[pl.ds(i, 1), :] - du_i * y_ref[pl.ds(i + 1, 1), :]
        ) / dhat_i
        v_ref[pl.ds(i, 1), :] = (
            v_ref[pl.ds(i, 1), :] - du_i * v_ref[pl.ds(i + 1, 1), :]
        ) / dhat_i
        w_ref[pl.ds(i, 1), :] = (
            w_ref[pl.ds(i, 1), :] - du_i * w_ref[pl.ds(i + 1, 1), :]
        ) / dhat_i
        return carry

    common.fori_loop(0, last, bwd)


def stage1_tiled(
    dlT: jax.Array,
    dT: jax.Array,
    duT: jax.Array,
    bT: jax.Array,
    *,
    m: int,
    block_p: int,
    interpret: bool,
):
    """Pallas call on (m, P) transposed blocked operands, P % block_p == 0."""
    _, p = dT.shape
    grid = (p // block_p,)
    in_spec = common.block_spec((m, block_p), lambda i: (0, i))
    out_spec = common.block_spec((m - 1, block_p), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((m - 1, p), dT.dtype)
    return pl.pallas_call(
        functools.partial(_stage1_kernel, m=m),
        grid=grid,
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 3,
        out_shape=[out_shape] * 3,
        scratch_shapes=[pltpu.VMEM((m - 1, block_p), dT.dtype)],
        interpret=interpret,
    )(dlT, dT, duT, bT)


def _stage1_kernel_wide(
    dl_ref, d_ref, du_ref, b_ref, y_ref, v_ref, w_ref, dhat_ref, *, m: int
):
    """Interleaved-layout body: tiles are (block rows, m, lane-block of
    systems). Same recurrence as ``_stage1_kernel`` along the middle (m)
    axis, vectorized over the leading block-row axis *and* the lanes — every
    lane is a different system, every leading row an independent block."""
    mi = m - 1

    dhat_ref[:, 0:1, :] = d_ref[:, 0:1, :]
    y_ref[:, 0:1, :] = b_ref[:, 0:1, :]
    v_ref[:, 0:1, :] = dl_ref[:, 0:1, :]
    w_ref[...] = jnp.zeros(w_ref.shape, w_ref.dtype)

    def fwd(i, carry):
        wgt = dl_ref[:, pl.ds(i, 1), :] / dhat_ref[:, pl.ds(i - 1, 1), :]
        dhat_ref[:, pl.ds(i, 1), :] = (
            d_ref[:, pl.ds(i, 1), :] - wgt * du_ref[:, pl.ds(i - 1, 1), :]
        )
        y_ref[:, pl.ds(i, 1), :] = (
            b_ref[:, pl.ds(i, 1), :] - wgt * y_ref[:, pl.ds(i - 1, 1), :]
        )
        v_ref[:, pl.ds(i, 1), :] = -wgt * v_ref[:, pl.ds(i - 1, 1), :]
        return carry

    common.fori_loop(1, mi, fwd)

    last = mi - 1
    dhat_last = dhat_ref[:, pl.ds(last, 1), :]
    y_ref[:, pl.ds(last, 1), :] = y_ref[:, pl.ds(last, 1), :] / dhat_last
    v_ref[:, pl.ds(last, 1), :] = v_ref[:, pl.ds(last, 1), :] / dhat_last
    w_ref[:, pl.ds(last, 1), :] = du_ref[:, pl.ds(last, 1), :] / dhat_last

    def bwd(j, carry):
        i = last - 1 - j
        du_i = du_ref[:, pl.ds(i, 1), :]
        dhat_i = dhat_ref[:, pl.ds(i, 1), :]
        y_ref[:, pl.ds(i, 1), :] = (
            y_ref[:, pl.ds(i, 1), :] - du_i * y_ref[:, pl.ds(i + 1, 1), :]
        ) / dhat_i
        v_ref[:, pl.ds(i, 1), :] = (
            v_ref[:, pl.ds(i, 1), :] - du_i * v_ref[:, pl.ds(i + 1, 1), :]
        ) / dhat_i
        w_ref[:, pl.ds(i, 1), :] = (
            w_ref[:, pl.ds(i, 1), :] - du_i * w_ref[:, pl.ds(i + 1, 1), :]
        ) / dhat_i
        return carry

    common.fori_loop(0, last, bwd)


def stage1_tiled_wide(
    dlw: jax.Array,
    dw: jax.Array,
    duw: jax.Array,
    bw: jax.Array,
    *,
    m: int,
    block_rows: int,
    block_b: int,
    interpret: bool,
):
    """Wide-batch grid over interleaved (P, m, B) operands.

    Grid = (B // block_b, P // block_rows): each step owns a lane-block of
    ``block_b`` systems × ``block_rows`` partition blocks — the batch axis is
    the minor/lane axis of every tile, so at B ≫ 1 the VPU lanes read
    contiguous (coalesced) data instead of the per-system strides of
    ``stage1_tiled_batched``.
    """
    p, _, bt = dw.shape
    grid = (bt // block_b, p // block_rows)
    in_spec = common.block_spec((block_rows, m, block_b), lambda bi, i: (i, 0, bi))
    out_spec = common.block_spec(
        (block_rows, m - 1, block_b), lambda bi, i: (i, 0, bi)
    )
    out_shape = jax.ShapeDtypeStruct((p, m - 1, bt), dw.dtype)
    return pl.pallas_call(
        functools.partial(_stage1_kernel_wide, m=m),
        grid=grid,
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 3,
        out_shape=[out_shape] * 3,
        scratch_shapes=[pltpu.VMEM((block_rows, m - 1, block_b), dw.dtype)],
        interpret=interpret,
    )(dlw, dw, duw, bw)


def stage1_tiled_batched(
    dlT: jax.Array,
    dT: jax.Array,
    duT: jax.Array,
    bT: jax.Array,
    *,
    m: int,
    block_p: int,
    interpret: bool,
):
    """Batched grid over (B, m, P) operands: grid = (B, P // block_p).

    The leading grid dimension walks the batch of independent systems; the
    block-spec squeezes it (block size ``None``), so the per-tile kernel body
    is shared with the single-system path. On TPU the flattened grid keeps
    the HBM→VMEM pipeline running across system boundaries — the multi-SLAE
    analogue of the paper's streams spanning the whole workload.
    """
    bsz, _, p = dT.shape
    grid = (bsz, p // block_p)
    in_spec = common.block_spec((None, m, block_p), lambda bi, i: (bi, 0, i))
    out_spec = common.block_spec((None, m - 1, block_p), lambda bi, i: (bi, 0, i))
    out_shape = jax.ShapeDtypeStruct((bsz, m - 1, p), dT.dtype)
    return pl.pallas_call(
        functools.partial(_stage1_kernel, m=m),
        grid=grid,
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 3,
        out_shape=[out_shape] * 3,
        scratch_shapes=[pltpu.VMEM((m - 1, block_p), dT.dtype)],
        interpret=interpret,
    )(dlT, dT, duT, bT)
