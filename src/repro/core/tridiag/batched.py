"""Batched multi-SLAE solving: many independent tridiagonal systems at once.

The production regime (ROADMAP north star; Gloster et al., Carroll et al. in
PAPERS.md) is not one giant SLAE but *many* concurrent ones — a request queue
of same-size systems that should be solved together so the chunk/stream
granularity is no longer limited by a single system's block count.

Key identity: **batch fusion by concatenation.** With the solver convention
``dl[0] = du[n-1] = 0``, the partition method applied to the concatenation of
B systems of size n is *exactly* the B independent solves:

- Stage 1 is per-block, so blocks of different systems never mix.
- The reduced interface system decouples at system boundaries: the first
  block of each system has a zero left spike (``v = B⁻¹(dl[0]·e₀) = 0`` ⇒
  ``red_dl = 0``) and the last block a zero right coupling (``cL = du[n-1] =
  0`` ⇒ ``red_du = 0``), so one Thomas sweep over the fused reduced system
  passes through every boundary with an exact zero elimination weight.
- Stage 3's cross-block term at a boundary is ``v·s_{p-1}`` with ``v = 0``.

So the batched solve reuses the single-system pipeline on the fused
``(B·n,)`` arrays, and chunks ("virtual streams") may span system boundaries
— the whole point of batching small systems.

API example (the facade ``repro.api.TridiagSession`` is the front door)::

    from repro.api import SolverConfig, TridiagSession
    from repro.core.tridiag.batched import solve_batched

    # functional, jit/vmap-friendly: (B, n) diagonals in, (B, n) solutions out
    x = solve_batched(dl, d, du, b, m=10)

    # chunked execution (the stream analogue), one fused dispatch
    session = TridiagSession(SolverConfig(m=10, num_chunks=8))
    x = session.solve_batched(dl, d, du, b)
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tridiag import partition
from repro.core.tridiag.thomas import thomas

Array = jax.Array


# --------------------------------------------------------------- functional --
def thomas_batched(dl: Array, d: Array, du: Array, b: Array) -> Array:
    """Shape-checked Thomas reference for a (B, n) batch: (B, n) → (B, n).

    ``thomas`` already supports leading batch dimensions; this wrapper just
    enforces the batched-API contract (exactly one batch axis)."""
    dl, d, du, b = (jnp.asarray(a) for a in (dl, d, du, b))
    if d.ndim != 2:
        raise ValueError(f"expected (batch, n) operands, got shape {d.shape}")
    return thomas(dl, d, du, b)


@partial(jax.jit, static_argnames=("m",))
def _solve_batched_impl(dl, d, du, b, *, m: int):
    return jax.vmap(partial(partition.partition_solve, m=m))(dl, d, du, b)


def solve_batched(dl: Array, d: Array, du: Array, b: Array, *, m: int = 10) -> Array:
    """Solve B independent systems via vmapped partition stages.

    Operands are (B, n) with the usual convention (``dl[:, 0]`` and
    ``du[:, n-1]`` ignored); returns the (B, n) solutions.
    """
    dl, d, du, b = (jnp.asarray(a) for a in (dl, d, du, b))
    if d.ndim != 2:
        raise ValueError(f"expected (batch, n) operands, got shape {d.shape}")
    n = d.shape[-1]
    if n % m:
        raise ValueError(f"system size {n} not divisible by m={m}")
    return _solve_batched_impl(dl, d, du, b, m=m)


# ------------------------------------------------------------- batch fusion --
def fuse_systems(
    dl: np.ndarray, d: np.ndarray, du: np.ndarray, b: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(B, n) batch → one fused (B·n,) system with boundary couplings zeroed.

    Zeroing ``dl[:, 0]`` / ``du[:, n-1]`` is what makes the fused partition
    solve decouple exactly (see module docstring); those entries are ignored
    by convention in the unfused solve, so this loses nothing. Device arrays
    are fused on their devices, keeping their sharding.
    """
    if isinstance(d, jax.Array):
        dl = jnp.asarray(dl).at[..., :, 0].set(0.0)
        du = jnp.asarray(du).at[..., :, -1].set(0.0)
        return tuple(
            jnp.asarray(a).reshape(*a.shape[:-2], -1) for a in (dl, d, du, b)
        )
    dl = np.array(dl, copy=True)
    du = np.array(du, copy=True)
    dl[..., :, 0] = 0.0
    du[..., :, -1] = 0.0
    def flat(a):
        return np.ascontiguousarray(np.asarray(a).reshape(*a.shape[:-2], -1))

    return flat(dl), flat(d), flat(du), flat(b)


def split_systems(x: np.ndarray, batch: int) -> np.ndarray:
    """Inverse of :func:`fuse_systems` for the solution vector."""
    return np.asarray(x).reshape(*x.shape[:-1], batch, x.shape[-1] // batch)

