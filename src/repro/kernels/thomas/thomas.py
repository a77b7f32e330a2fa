"""Batched Thomas solve as a Pallas TPU kernel.

Layout: the solve dimension n lives on sublanes (axis 0), the batch dimension
on lanes (axis 1, tiled in multiples of 128). Each grid step owns a
(n, block_b) VMEM tile of all four operands; successive grid steps are
double-buffered by the Pallas pipeline (HBM→VMEM DMA of tile i+1 overlaps the
recurrence of tile i — the TPU analogue of the paper's stream overlap).

VMEM budget per grid step: 7 tiles of (n, block_b) (4 in, 1 out, 2 scratch),
n rounded up to whole 8-row sublane tiles. With fp32, n=512, block_b=256 that
is ~3.6 MiB; ``ops.thomas_fits_vmem`` holds callers to the 16 MiB scoped
limit, which a v5e compile reaches at n = 4,681 on one 128-lane tile.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _thomas_kernel(dl_ref, d_ref, du_ref, b_ref, x_ref, dhat_ref, bhat_ref, *, n: int):
    """Solve along axis 0 of (n, bb) tiles."""
    dhat_ref[0:1, :] = d_ref[0:1, :]
    bhat_ref[0:1, :] = b_ref[0:1, :]

    def fwd(i, carry):
        w = dl_ref[pl.ds(i, 1), :] / dhat_ref[pl.ds(i - 1, 1), :]
        dhat_ref[pl.ds(i, 1), :] = d_ref[pl.ds(i, 1), :] - w * du_ref[pl.ds(i - 1, 1), :]
        bhat_ref[pl.ds(i, 1), :] = b_ref[pl.ds(i, 1), :] - w * bhat_ref[pl.ds(i - 1, 1), :]
        return carry

    common.fori_loop(1, n, fwd)

    x_ref[pl.ds(n - 1, 1), :] = (
        bhat_ref[pl.ds(n - 1, 1), :] / dhat_ref[pl.ds(n - 1, 1), :]
    )

    def bwd(j, carry):
        i = n - 2 - j
        x_ref[pl.ds(i, 1), :] = (
            bhat_ref[pl.ds(i, 1), :]
            - du_ref[pl.ds(i, 1), :] * x_ref[pl.ds(i + 1, 1), :]
        ) / dhat_ref[pl.ds(i, 1), :]
        return carry

    common.fori_loop(0, n - 1, bwd)


def thomas_tiled(
    dlT: jax.Array,
    dT: jax.Array,
    duT: jax.Array,
    bT: jax.Array,
    *,
    block_b: int,
    interpret: bool,
) -> jax.Array:
    """Pallas call on transposed operands of shape (n, B), B % block_b == 0."""
    n, bt = dlT.shape
    grid = (bt // block_b,)
    spec = common.block_spec((n, block_b), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_thomas_kernel, n=n),
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, bt), dT.dtype),
        scratch_shapes=[
            pltpu.VMEM((n, block_b), dT.dtype),
            pltpu.VMEM((n, block_b), dT.dtype),
        ],
        interpret=interpret,
    )(dlT, dT, duT, bT)
