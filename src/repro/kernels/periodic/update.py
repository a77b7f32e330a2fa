"""The Sherman–Morrison correction of a cyclic reduced solve as a Pallas
TPU kernel: x = y - β z, with one β per system.

``y`` and ``z`` are the solutions of the corner-free reduced system for the
right-hand side and for the rank-one vector u
(:func:`repro.core.tridiag.partition.cyclic_solve`). Each grid step owns a
(block_rows, block_cols) tile of both; β is one row of lanes or one column
of sublanes of the tile, broadcast along the solve axis. The kernel is a
bandwidth-bound fused multiply-add: two tiles in, one out.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl

from repro.kernels import common


def _update_kernel(y_ref, z_ref, beta_ref, x_ref):
    x_ref[...] = y_ref[...] - beta_ref[...] * z_ref[...]


def update_tiled(
    y: jax.Array,
    z: jax.Array,
    beta: jax.Array,
    *,
    axis: int,
    block_rows: int,
    block_cols: int,
    interpret: bool,
) -> jax.Array:
    """Pallas call on (R, C) tiles, R % block_rows == C % block_cols == 0;
    ``beta`` is (1, C) for solve ``axis`` 0, (R, 1) for ``axis`` 1."""
    r, c = y.shape
    grid = (r // block_rows, c // block_cols)
    spec = common.block_spec((block_rows, block_cols), lambda i, j: (i, j))
    if axis == 0:
        beta_spec = common.block_spec((1, block_cols), lambda i, j: (0, j))
    else:
        beta_spec = common.block_spec((block_rows, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[spec, spec, beta_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, c), y.dtype),
        interpret=interpret,
    )(y, z, beta)
