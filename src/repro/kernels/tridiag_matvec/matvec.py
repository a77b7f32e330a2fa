"""Tridiagonal matvec r = A·x as a Pallas TPU kernel (residual checks).

The stencil shifts are materialized outside the kernel (XLA pad/slice); the
kernel is the bandwidth-bound fused multiply-add over 128-lane tiles.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl

from repro.kernels import common


def _matvec_kernel(dl_ref, d_ref, du_ref, xl_ref, x_ref, xr_ref, r_ref):
    r_ref[...] = (
        dl_ref[...] * xl_ref[...]
        + d_ref[...] * x_ref[...]
        + du_ref[...] * xr_ref[...]
    )


def matvec_tiled(
    dl2, d2, du2, xl2, x2, xr2, *, block_r: int, interpret: bool
) -> jax.Array:
    """All operands pre-reshaped to (R, 128); tiles of (block_r, 128)."""
    r, lanes = d2.shape
    grid = (r // block_r,)
    spec = common.block_spec((block_r, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        _matvec_kernel,
        grid=grid,
        in_specs=[spec] * 6,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, lanes), d2.dtype),
        interpret=interpret,
    )(dl2, d2, du2, xl2, x2, xr2)
