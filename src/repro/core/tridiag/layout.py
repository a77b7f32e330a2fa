"""Operand layouts for the fused batch axis (system-major vs interleaved).

The executors consume a batch of tridiagonal systems as four fused 1-D
operands (``Σnᵢ`` elements, systems concatenated — see ``ragged.fuse_ragged``).
That *system-major* order keeps each system contiguous, which is what the
chunked/staged path slices. But for the stage kernels it feeds the vector
lanes strided data: the natural SIMD axis at B ≫ 1 is the *batch* axis.

The *interleaved* (lane-major) layout fixes that. Operands are regathered to

    wide[p, r, i]  =  operand of system ``i``, block ``p``, in-block row ``r``

i.e. shape ``(P, m, B)`` with the systems on the minor (lane) axis — the jax
rendering of the coalesced layout from "Efficient Interleaved Batch Matrix
Solvers for CUDA" (PAPERS.md, 1909.04539). Consequences:

- stage-1/stage-3 tiles become ``(block of systems) × (block row)`` with B on
  lanes — every lane works a different system at the same local row;
- the stage-2 reduced solve becomes B *parallel* scans of length P (shape
  ``(P, B)``, solve axis 0) instead of one serial scan of length ``Σ Pᵢ``
  — the dominant win, on every backend;
- ragged batches pad each system to ``P_max`` blocks with identity blocks
  (dl=0, d=1, du=0, b=0). Padding is exact, not approximate: fused ragged
  operands have each system's boundary couplings zeroed, so identity blocks
  produce zero spikes, a decoupled unit row in the reduced system, and s=0.

Both transforms are pure ``jnp`` gathers/reshapes built from *static* index
maps, so they trace into the fused executable — callers and the serving
engine never observe the transposed layout, and ``donate_argnums`` still
refers to the caller-visible 1-D buffers.

Layout selection (``resolve_layout``): ``"auto"`` interleaves only flat (no
stacked leading dims) batches of at least :data:`AUTO_INTERLEAVE_MIN_BATCH`
systems, and only when ragged padding would not blow the footprint up past
:data:`AUTO_INTERLEAVE_MAX_WASTE`.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tridiag.partition import PartitionCoeffs, partition_stage1, prev_block
from repro.core.tridiag.thomas import thomas

Array = jax.Array

LAYOUTS = ("system-major", "interleaved", "auto")

# "auto" interleaves a fused batch only at B >= this (one VPU lane-quarter —
# below that the gather costs more than the wide scans save).
AUTO_INTERLEAVE_MIN_BATCH = 32

# ... and only while identity-padding ragged systems to P_max blocks inflates
# the operand footprint by at most this factor.
AUTO_INTERLEAVE_MAX_WASTE = 1.5


def resolve_layout(
    layout: str,
    sizes: Sequence[int],
    m: int,
    *,
    lead_ndim: int = 0,
    batch_shards: int = 1,
) -> str:
    """Resolve a config layout to a concrete one for a given batch.

    ``lead_ndim`` is the number of stacked leading dims on the operands
    (``solve`` on (K, n) inputs). The interleave transforms are defined on
    flat fused operands only, so stacked inputs always stay system-major —
    explicitly requesting ``"interleaved"`` for them is an error rather than
    a silent fallback.

    ``batch_shards`` is the lane-axis shard count a mesh-configured executor
    would split the batch over: the ``"auto"`` threshold compares the
    *per-shard* lane count (each device's wide grid only ever sees
    ``B / batch_shards`` systems), so turning a mesh on can't silently flip
    a mid-sized batch into lanes too narrow to pay for the gathers.
    """
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
    if batch_shards < 1:
        raise ValueError(f"batch_shards must be >= 1, got {batch_shards}")
    if layout == "system-major":
        return "system-major"
    if layout == "interleaved":
        if lead_ndim:
            raise ValueError(
                "layout='interleaved' requires flat fused operands; got "
                f"{lead_ndim} stacked leading dim(s) — use solve_batched/"
                "solve_many or layout='system-major'"
            )
        return "interleaved"
    # auto
    if lead_ndim:
        return "system-major"
    bsz = len(sizes)
    if bsz // batch_shards < AUTO_INTERLEAVE_MIN_BATCH:
        return "system-major"
    total = sum(sizes)
    padded = max(n // m for n in sizes) * m * bsz
    if padded > AUTO_INTERLEAVE_MAX_WASTE * total:
        return "system-major"
    return "interleaved"


def _check_sizes(sizes: Sequence[int], m: int) -> Tuple[int, ...]:
    sizes = tuple(int(n) for n in sizes)
    if not sizes:
        raise ValueError("sizes must name at least one system")
    for n in sizes:
        if n <= 0 or n % m:
            raise ValueError(f"system size {n} not divisible by m={m}")
    return sizes


@functools.lru_cache(maxsize=512)
def _index_maps(
    sizes: Tuple[int, ...], m: int
) -> Tuple[np.ndarray, np.ndarray, bool]:
    """Static gather maps for one fused batch shape.

    Returns ``(fwd, inv, uniform)``: ``fwd`` is (P_max, m, B) int32 into the
    fused array extended with one fill slot at index ``total``; ``inv`` is
    (total,) int32 into the flattened (P_max*m*B,) wide array. Cached — the
    serving engine replays a small set of batch shapes.
    """
    sizes = _check_sizes(sizes, m)
    bsz = len(sizes)
    total = sum(sizes)
    p_max = max(n // m for n in sizes)
    fwd = np.full((p_max * m, bsz), total, dtype=np.int32)
    inv = np.empty(total, dtype=np.int32)
    off = 0
    for i, n in enumerate(sizes):
        rows = np.arange(n, dtype=np.int32)
        fwd[:n, i] = off + rows
        # wide flat index of (p, r, i) is (p*m + r)*B + i = row*B + i
        inv[off : off + n] = rows * bsz + i
        off += n
    uniform = len(set(sizes)) == 1
    return fwd.reshape(p_max, m, bsz), inv, uniform


def interleave(a: Array, sizes: Sequence[int], m: int, *, fill: float = 0.0) -> Array:
    """Regather one fused 1-D operand (Σnᵢ,) to wide (P_max, m, B).

    Ragged systems are padded with ``fill`` (use 1.0 for the diagonal so
    padded blocks are identity rows and never divide by zero).
    """
    sizes = _check_sizes(sizes, m)
    a = jnp.asarray(a)
    fwd, _, uniform = _index_maps(sizes, m)
    if uniform:
        # Pure reshape/transpose — no gather, no fill needed. One 2-D
        # transpose (B, n) -> (n, B), then the row split: nothing in between
        # holds ``m`` on the minor (lane) axis, which the TPU's (8, 128)
        # tiling would pad 128/m-fold at small ``m``.
        bsz = len(sizes)
        n = sizes[0]
        return a.reshape(bsz, n).T.reshape(n // m, m, bsz)
    a_ext = jnp.concatenate([a, jnp.full((1,), fill, a.dtype)])
    return jnp.take(a_ext, fwd, axis=0)


def interleave_operands(
    dl: Array, d: Array, du: Array, b: Array, sizes: Sequence[int], m: int
) -> Tuple[Array, Array, Array, Array]:
    """Interleave all four fused operands; padding forms identity blocks."""
    return (
        interleave(dl, sizes, m, fill=0.0),
        interleave(d, sizes, m, fill=1.0),
        interleave(du, sizes, m, fill=0.0),
        interleave(b, sizes, m, fill=0.0),
    )


def deinterleave(xw: Array, sizes: Sequence[int], m: int) -> Array:
    """Regather a wide (P_max, m, B) solution back to fused 1-D (Σnᵢ,)."""
    sizes = _check_sizes(sizes, m)
    xw = jnp.asarray(xw)
    _, inv, uniform = _index_maps(sizes, m)
    if uniform:
        # The reverse of interleave's lane-dense path: (P, m, B) -> (n, B),
        # one transpose to (B, n), then flat.
        p, _, bsz = xw.shape
        return xw.reshape(p * m, bsz).T.reshape(-1)
    return jnp.take(xw.reshape(-1), inv, axis=0)


# ---------------------------------------------------------------------------
# Reference (pure jnp) wide stage implementations. Same algebra as
# partition.py, expressed on (P, m, B) operands; the reduced solve runs B
# parallel length-P scans. These back ``StageBackend.make_wide_*`` defaults,
# so every backend (including user subclasses) supports the interleaved
# layout out of the box.
# ---------------------------------------------------------------------------


def partition_stage1_wide(
    dlw: Array, dw: Array, duw: Array, bw: Array, *, m: int, periodic: bool = False
) -> PartitionCoeffs:
    """Stage 1 on wide operands → wide coeffs: spikes (P, m-1, B), reduced
    rows (P, B). Delegates to the batch-polymorphic system-major stage via
    transposes (XLA folds these into the surrounding gathers); ``periodic``
    as there."""
    p, _, bsz = dw.shape

    def to_sys(a: Any) -> Any:
        return a.transpose(2, 0, 1).reshape(bsz, p * m)

    def spike(a: Any) -> Any:  # (B, P, m-1) -> (P, m-1, B)
        return a.transpose(1, 2, 0)

    c = partition_stage1(
        to_sys(dlw), to_sys(dw), to_sys(duw), to_sys(bw), m, periodic
    )
    return PartitionCoeffs(
        spike(c.y), spike(c.v), spike(c.w),
        c.red_dl.T, c.red_d.T, c.red_du.T, c.red_b.T,
    )


def thomas_wide(red_dl: Array, red_d: Array, red_du: Array, red_b: Array) -> Array:
    """Reduced solve on (P, B) rows: B parallel Thomas scans along axis 0."""
    return thomas(red_dl.T, red_d.T, red_du.T, red_b.T).T


def partition_stage3_wide(
    coeffs: PartitionCoeffs, s: Array, periodic: bool = False
) -> Array:
    """Back-substitution on wide coeffs + (P, B) interface values → (P, m, B).

    ``s_left`` is a shift along the block axis; row 0 of every column is a
    system's first block, so the zero boundary is exact for every system
    (and the roll round to its last block exact when ``periodic``).
    """
    s_left = prev_block(s, axis=0, periodic=periodic)
    x_int = (
        coeffs.y - coeffs.v * s_left[:, None, :] - coeffs.w * s[:, None, :]
    )
    return jnp.concatenate([x_int, s[:, None, :]], axis=1)
