"""Jitted wrapper for the Sherman–Morrison correction kernel.

``PallasBackend`` hands :func:`periodic_correction_pallas` to
:func:`repro.core.tridiag.partition.cyclic_solve` as its update, so the
correction of a periodic fused solve runs on device as one kernel. On a
TPU trace the kernel is a custom call named after the jitted wrapper,
``_periodic_correction`` (:data:`repro.core.tridiag.spans.PERIODIC_KERNEL`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.periodic.update import update_tiled


@functools.partial(
    jax.jit, static_argnames=("axis", "block_rows", "block_cols", "interpret")
)
def _periodic_correction(
    y, z, beta, *, axis: int, block_rows: int, block_cols: int, interpret: bool
):
    r, c = y.shape
    rp, cp = common.round_up(r, block_rows), common.round_up(c, block_cols)
    def pad(a):
        return common.pad_axis_to(common.pad_axis_to(a, rp, axis=0), cp, axis=1)

    # beta runs along the axis that is not the solve axis
    beta = common.pad_axis_to(beta, (cp, rp)[axis], axis=1 - axis)
    x = update_tiled(
        pad(y), pad(z), beta,
        axis=axis, block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
    return x[:r, :c]


def periodic_correction_pallas(
    y: jax.Array,
    z: jax.Array,
    beta: jax.Array,
    *,
    axis: int,
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``y - beta * z`` for (R, C) ``y`` and ``z`` solved along ``axis``, and
    a ``beta`` of one value per system: ``(1, C)`` for ``axis=0``, ``(R, 1)``
    for ``axis=1``. Tiled over (R, C)."""
    if interpret is None:
        interpret = common.interpret_default()
    y, z, beta = (jnp.asarray(a) for a in (y, z, beta))
    if y.ndim != 2 or z.shape != y.shape:
        raise ValueError(f"expected two equal 2-D operands, got {y.shape}, {z.shape}")
    r, c = y.shape
    want = ((1, c), (r, 1))[axis]
    if beta.shape != want:
        raise ValueError(f"beta along axis {axis} must be {want}, got {beta.shape}")
    block_rows = min(block_rows, common.round_up(r, common.SUBLANES))
    block_cols = min(block_cols, common.round_up(c, common.LANES))
    return _periodic_correction(
        y, z, beta.astype(y.dtype),
        axis=axis, block_rows=block_rows, block_cols=block_cols, interpret=interpret,
    )
