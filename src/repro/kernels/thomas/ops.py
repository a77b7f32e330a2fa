"""Jitted public wrapper for the batched Thomas Pallas kernel.

Besides its original role (B independent solves), this kernel is the
device-side Stage-2 reduced solver of the fused dispatch path:
``repro.core.tridiag.plan.PallasBackend.make_reduced_solve`` traces
:func:`thomas_pallas` into the single-dispatch fused executable (1-D reduced
systems ride the batch-1 path below) while :func:`thomas_fits_vmem` holds.
Beyond it the backend partitions the reduced system on the Stage-1 and
Stage-3 kernels until what is left fits, and solves that here
(:func:`repro.core.tridiag.partition.partition_solve_recursive`), so a fused
Pallas solve keeps all three partition stages on device, in kernels, at
every size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.thomas.thomas import thomas_tiled

#: Scoped VMEM one Mosaic kernel may use by default on TPU v5e.
VMEM_BUDGET_BYTES = 16 * 2**20
#: (n, block_b) tiles the kernel keeps resident: 4 in, 1 out, 2 scratch.
RESIDENT_TILES = 7


def thomas_vmem_bytes(n: int, lanes: int, itemsize: int, block_b: int = 256) -> int:
    """VMEM one grid step of :func:`thomas_tiled` takes for ``lanes``
    systems of ``n`` rows, with the lane block the wrappers below pick."""
    block_b = min(block_b, common.round_up(lanes, common.LANES))
    return RESIDENT_TILES * common.round_up(n, common.SUBLANES) * block_b * itemsize


def thomas_fits_vmem(n: int, lanes: int, itemsize: int, block_b: int = 256) -> bool:
    """Whether the kernel compiles for these shapes, decided before tracing.

    The 1-D fused reduced system rides one 128-lane tile, so on fp32 it
    fits up to n = 4,680 rows (a v5e compile at 4,681 runs out of VMEM).
    """
    return thomas_vmem_bytes(n, lanes, itemsize, block_b) <= VMEM_BUDGET_BYTES


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _thomas_impl(dl, d, du, b, *, block_b: int, interpret: bool):
    bsz, n = d.shape
    bp = common.round_up(bsz, block_b)
    # Pad batch with identity systems (d=1) so padded lanes never divide by 0.
    dlT = common.pad_axis_to(dl.T, bp, axis=1)
    dT = common.pad_axis_to(d.T, bp, axis=1, value=1.0)
    duT = common.pad_axis_to(du.T, bp, axis=1)
    bT = common.pad_axis_to(b.T, bp, axis=1)
    xT = thomas_tiled(dlT, dT, duT, bT, block_b=block_b, interpret=interpret)
    return xT[:, :bsz].T


def thomas_pallas(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Solve B independent tridiagonal systems given as (B, n) diagonals."""
    if interpret is None:
        interpret = common.interpret_default()
    dl, d, du, b = (jnp.asarray(a) for a in (dl, d, du, b))
    if d.ndim == 1:
        return thomas_pallas(
            dl[None], d[None], du[None], b[None],
            block_b=block_b, interpret=interpret,
        )[0]
    block_b = min(block_b, common.round_up(d.shape[0], common.LANES))
    return _thomas_impl(dl, d, du, b, block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _thomas_impl_wide(dl, d, du, b, *, block_b: int, interpret: bool):
    _, bsz = d.shape
    bp = common.round_up(bsz, block_b)
    # Identity-pad the lane axis (d=1) so padded lanes never divide by 0.
    dlw = common.pad_axis_to(dl, bp, axis=1)
    dw = common.pad_axis_to(d, bp, axis=1, value=1.0)
    duw = common.pad_axis_to(du, bp, axis=1)
    bw = common.pad_axis_to(b, bp, axis=1)
    xw = thomas_tiled(dlw, dw, duw, bw, block_b=block_b, interpret=interpret)
    return xw[:, :bsz]


def thomas_pallas_wide(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Lane-major Thomas: (n, B) operands already interleaved, solve axis 0.

    The Stage-2 reduced solver of the interleaved fused path: the wide
    reduced rows come out of ``partition_stage1_pallas_wide`` as (P, B) and
    go straight onto the lanes with no transpose — grid tiles are lane-blocks
    of systems, so B parallel length-P scans replace one serial Σ Pᵢ scan.
    """
    if interpret is None:
        interpret = common.interpret_default()
    dl, d, du, b = (jnp.asarray(a) for a in (dl, d, du, b))
    if d.ndim != 2:
        raise ValueError(f"expected interleaved (n, B) operands, got {d.shape}")
    block_b = min(block_b, common.round_up(d.shape[1], common.LANES))
    return _thomas_impl_wide(dl, d, du, b, block_b=block_b, interpret=interpret)
