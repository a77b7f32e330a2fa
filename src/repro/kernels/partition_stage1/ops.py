"""Jitted wrapper: Stage-1 Pallas kernel + reduced-row assembly.

``periodic`` (static) assembles the reduced rows of cyclic systems: the last
block's right neighbour is block 0, so the next-block shift rolls along the
block axis instead of filling with zeros (``partition.py``). The kernel is
the same either way; non-periodic calls trace exactly as without the flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.tridiag.partition import PartitionCoeffs
from repro.kernels import common
from repro.kernels.partition_stage1.stage1 import (
    stage1_tiled,
    stage1_tiled_batched,
    stage1_tiled_wide,
)


@functools.partial(jax.jit, static_argnames=("m", "block_p", "interpret", "periodic"))
def _stage1_impl(
    dl, d, du, b, *, m: int, block_p: int, interpret: bool, periodic: bool = False
):
    n = d.shape[-1]
    p = n // m
    pp = common.round_up(p, block_p)
    def blk(a, fill):  # (m, pp)
        return common.pad_axis_to(a.reshape(p, m).T, pp, axis=1, value=fill)

    dlT, dT, duT, bT = blk(dl, 0.0), blk(d, 1.0), blk(du, 0.0), blk(b, 0.0)
    yT, vT, wT = stage1_tiled(
        dlT, dT, duT, bT, m=m, block_p=block_p, interpret=interpret
    )
    y, v, w = (a[:, :p].T for a in (yT, vT, wT))  # (p, m-1)

    # ---- reduced interface rows (cheap; same algebra as partition.py) ----
    dlb, db, dub, bb = (a.reshape(p, m) for a in (dl, d, du, b))
    aL, bL, cL, dL = dlb[:, m - 1], db[:, m - 1], dub[:, m - 1], bb[:, m - 1]
    def pad(a):
        if periodic:
            return jnp.roll(a[:, 0], -1)
        return jnp.concatenate([a[1:, 0], jnp.zeros_like(a[:1, 0])])

    y_nf, v_nf, w_nf = pad(y), pad(v), pad(w)
    red_dl = -aL * v[:, m - 2]
    red_d = bL - aL * w[:, m - 2] - cL * v_nf
    red_du = -cL * w_nf
    red_b = dL - aL * y[:, m - 2] - cL * y_nf
    return PartitionCoeffs(y, v, w, red_dl, red_d, red_du, red_b)


def partition_stage1_pallas(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    m: int = 10,
    block_p: int = 512,
    interpret: bool | None = None,
    periodic: bool = False,
) -> PartitionCoeffs:
    """Stage 1 of the partition method for a single (N,) system via Pallas
    (a cyclic one with ``periodic``)."""
    if interpret is None:
        interpret = common.interpret_default()
    dl, d, du, b = (jnp.asarray(a) for a in (dl, d, du, b))
    n = d.shape[-1]
    if n % m:
        raise ValueError(f"system size {n} not divisible by m={m}")
    block_p = min(block_p, common.round_up(n // m, common.LANES))
    return _stage1_impl(
        dl, d, du, b, m=m, block_p=block_p, interpret=interpret, periodic=periodic
    )


@functools.partial(jax.jit, static_argnames=("m", "block_p", "interpret", "periodic"))
def _stage1_impl_batched(
    dl, d, du, b, *, m: int, block_p: int, interpret: bool, periodic: bool = False
):
    bsz, n = d.shape
    p = n // m
    pp = common.round_up(p, block_p)
    def blk(a, fill):  # (B, m, pp)
        return common.pad_axis_to(
            a.reshape(bsz, p, m).transpose(0, 2, 1), pp, axis=2, value=fill
        )

    dlT, dT, duT, bT = blk(dl, 0.0), blk(d, 1.0), blk(du, 0.0), blk(b, 0.0)
    yT, vT, wT = stage1_tiled_batched(
        dlT, dT, duT, bT, m=m, block_p=block_p, interpret=interpret
    )
    y, v, w = (a[:, :, :p].transpose(0, 2, 1) for a in (yT, vT, wT))  # (B, p, m-1)

    # ---- reduced interface rows, vectorized over the batch axis ----
    dlb, db, dub, bb = (a.reshape(bsz, p, m) for a in (dl, d, du, b))
    aL, bL, cL, dL = dlb[:, :, m - 1], db[:, :, m - 1], dub[:, :, m - 1], bb[:, :, m - 1]
    def pad(a):
        if periodic:
            return jnp.roll(a[:, :, 0], -1, axis=1)
        return jnp.concatenate(
            [a[:, 1:, 0], jnp.zeros_like(a[:, :1, 0])], axis=1
        )

    y_nf, v_nf, w_nf = pad(y), pad(v), pad(w)
    red_dl = -aL * v[:, :, m - 2]
    red_d = bL - aL * w[:, :, m - 2] - cL * v_nf
    red_du = -cL * w_nf
    red_b = dL - aL * y[:, :, m - 2] - cL * y_nf
    return PartitionCoeffs(y, v, w, red_dl, red_d, red_du, red_b)


@functools.partial(
    jax.jit, static_argnames=("m", "block_rows", "block_b", "interpret", "periodic")
)
def _stage1_impl_wide(
    dlw, dw, duw, bw, *, m: int, block_rows: int, block_b: int, interpret: bool,
    periodic: bool = False,
):
    p, _, bsz = dw.shape
    pr = common.round_up(p, block_rows)
    bp = common.round_up(bsz, block_b)
    # Pad lanes and block rows with identity rows (d=1) — never divides by 0.
    def pad(a, fill):
        return common.pad_axis_to(
            common.pad_axis_to(a, bp, axis=2, value=fill), pr, axis=0, value=fill
        )

    yw, vw, ww = stage1_tiled_wide(
        pad(dlw, 0.0), pad(dw, 1.0), pad(duw, 0.0), pad(bw, 0.0),
        m=m, block_rows=block_rows, block_b=block_b, interpret=interpret,
    )
    yw, vw, ww = (a[:p, :, :bsz] for a in (yw, vw, ww))

    # ---- reduced interface rows, (P, B) wide; the cross-block shift runs
    # along axis 0 = the block axis of each lane's system ----
    aL, bL, cL, dL = dlw[:, m - 1, :], dw[:, m - 1, :], duw[:, m - 1, :], bw[:, m - 1, :]
    def nxt(a):
        if periodic:
            return jnp.roll(a[:, 0, :], -1, axis=0)
        return jnp.concatenate(
            [a[1:, 0, :], jnp.zeros_like(a[:1, 0, :])], axis=0
        )

    y_nf, v_nf, w_nf = nxt(yw), nxt(vw), nxt(ww)
    red_dl = -aL * vw[:, m - 2, :]
    red_d = bL - aL * ww[:, m - 2, :] - cL * v_nf
    red_du = -cL * w_nf
    red_b = dL - aL * yw[:, m - 2, :] - cL * y_nf
    return PartitionCoeffs(yw, vw, ww, red_dl, red_d, red_du, red_b)


def partition_stage1_pallas_wide(
    dlw: jax.Array,
    dw: jax.Array,
    duw: jax.Array,
    bw: jax.Array,
    *,
    m: int = 10,
    block_rows: int = 32,
    block_b: int = 256,
    interpret: bool | None = None,
    periodic: bool = False,
) -> PartitionCoeffs:
    """Stage 1 on batch-interleaved (P, m, B) operands (systems on lanes),
    of cyclic systems with ``periodic``.

    Returns wide coeffs: spikes (P, m-1, B), reduced rows (P, B). See
    ``repro.core.tridiag.layout`` for the layout contract and the exactness
    of identity-block padding for ragged batches.
    """
    if interpret is None:
        interpret = common.interpret_default()
    dlw, dw, duw, bw = (jnp.asarray(a) for a in (dlw, dw, duw, bw))
    if dw.ndim != 3 or dw.shape[1] != m:
        raise ValueError(
            f"expected interleaved (P, m={m}, B) operands, got shape {dw.shape}"
        )
    p, _, bsz = dw.shape
    block_b = min(block_b, common.round_up(bsz, common.LANES))
    block_rows = min(block_rows, common.round_up(p, common.SUBLANES))
    return _stage1_impl_wide(
        dlw, dw, duw, bw,
        m=m, block_rows=block_rows, block_b=block_b, interpret=interpret,
        periodic=periodic,
    )


def partition_stage1_pallas_batched(
    dl: jax.Array,
    d: jax.Array,
    du: jax.Array,
    b: jax.Array,
    *,
    m: int = 10,
    block_p: int = 512,
    interpret: bool | None = None,
    periodic: bool = False,
) -> PartitionCoeffs:
    """Stage 1 for a (B, N) batch of systems via one batched-grid Pallas call
    (cyclic systems with ``periodic``)."""
    if interpret is None:
        interpret = common.interpret_default()
    dl, d, du, b = (jnp.asarray(a) for a in (dl, d, du, b))
    if d.ndim != 2:
        raise ValueError(f"expected (batch, n) operands, got shape {d.shape}")
    n = d.shape[-1]
    if n % m:
        raise ValueError(f"system size {n} not divisible by m={m}")
    block_p = min(block_p, common.round_up(n // m, common.LANES))
    return _stage1_impl_batched(
        dl, d, du, b, m=m, block_p=block_p, interpret=interpret, periodic=periodic
    )
