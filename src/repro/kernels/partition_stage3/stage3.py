"""Partition-method Stage 3 (back-substitution) as a Pallas TPU kernel.

x_interior = y − v·s_{p−1} − w·s_p per block, plus the interface row itself.
Pure fused-multiply-add over (m−1, block_p) tiles with two broadcast rows —
memory-bound, exactly the operation the paper hides behind the Stage-3 D2H
transfer via streams.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels import common


def _stage3_kernel(y_ref, v_ref, w_ref, s_ref, sl_ref, x_ref, *, m: int):
    s = s_ref[0:1, :]
    sl = sl_ref[0:1, :]
    x_ref[0 : m - 1, :] = y_ref[...] - v_ref[...] * sl - w_ref[...] * s
    x_ref[m - 1 : m, :] = s


def stage3_tiled(
    yT: jax.Array,
    vT: jax.Array,
    wT: jax.Array,
    s: jax.Array,
    s_left: jax.Array,
    *,
    m: int,
    block_p: int,
    interpret: bool,
) -> jax.Array:
    """(m-1, P) spikes + (1, P) interface rows -> (m, P) solution tile."""
    p = s.shape[-1]
    grid = (p // block_p,)
    spike_spec = common.block_spec((m - 1, block_p), lambda i: (0, i))
    row_spec = common.block_spec((1, block_p), lambda i: (0, i))
    out_spec = common.block_spec((m, block_p), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_stage3_kernel, m=m),
        grid=grid,
        in_specs=[spike_spec] * 3 + [row_spec] * 2,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, p), yT.dtype),
        interpret=interpret,
    )(yT, vT, wT, s, s_left)


def _stage3_kernel_wide(y_ref, v_ref, w_ref, s_ref, sl_ref, x_ref, *, m: int):
    """Interleaved-layout body on (block rows, m-1, lane-block) spike tiles
    with (block rows, 1, lane-block) interface rows broadcast over axis 1."""
    s = s_ref[...]
    sl = sl_ref[...]
    x_ref[:, 0 : m - 1, :] = y_ref[...] - v_ref[...] * sl - w_ref[...] * s
    x_ref[:, m - 1 : m, :] = s


def stage3_tiled_wide(
    yw: jax.Array,
    vw: jax.Array,
    ww: jax.Array,
    s: jax.Array,
    s_left: jax.Array,
    *,
    m: int,
    block_rows: int,
    block_b: int,
    interpret: bool,
) -> jax.Array:
    """Wide-batch grid: interleaved (P, m-1, B) spikes + (P, 1, B) interface
    values → (P, m, B) solution. Grid = (B // block_b, P // block_rows); the
    systems ride the lanes (see ``stage1_tiled_wide``)."""
    p, _, bt = yw.shape
    grid = (bt // block_b, p // block_rows)
    spike_spec = common.block_spec(
        (block_rows, m - 1, block_b), lambda bi, i: (i, 0, bi)
    )
    row_spec = common.block_spec((block_rows, 1, block_b), lambda bi, i: (i, 0, bi))
    out_spec = common.block_spec((block_rows, m, block_b), lambda bi, i: (i, 0, bi))
    return pl.pallas_call(
        functools.partial(_stage3_kernel_wide, m=m),
        grid=grid,
        in_specs=[spike_spec] * 3 + [row_spec] * 2,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((p, m, bt), yw.dtype),
        interpret=interpret,
    )(yw, vw, ww, s, s_left)


def stage3_tiled_batched(
    yT: jax.Array,
    vT: jax.Array,
    wT: jax.Array,
    s: jax.Array,
    s_left: jax.Array,
    *,
    m: int,
    block_p: int,
    interpret: bool,
) -> jax.Array:
    """Batched grid over (B, m-1, P) spikes + (B, 1, P) interface rows.

    Mirror of ``stage1_tiled_batched``: leading grid dim over the batch,
    squeezed out of every block so the kernel body is shared.
    """
    bsz, _, p = yT.shape
    grid = (bsz, p // block_p)
    spike_spec = common.block_spec((None, m - 1, block_p), lambda bi, i: (bi, 0, i))
    row_spec = common.block_spec((None, 1, block_p), lambda bi, i: (bi, 0, i))
    out_spec = common.block_spec((None, m, block_p), lambda bi, i: (bi, 0, i))
    return pl.pallas_call(
        functools.partial(_stage3_kernel, m=m),
        grid=grid,
        in_specs=[spike_spec] * 3 + [row_spec] * 2,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, m, p), yT.dtype),
        interpret=interpret,
    )(yT, vT, wT, s, s_left)
