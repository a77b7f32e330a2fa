"""Ragged mixed-size batch fusion: heterogeneous systems in one fused solve.

`batched.py` fuses B *same-size* systems by concatenation; the decoupling
identity it rests on never uses the equal sizes. With the solver convention
``dl[0] = du[n-1] = 0``, concatenating systems of *any* sizes n₁..n_B gives a
``Σ nᵢ``-row tridiagonal system whose partition solve is exactly the B
independent solves:

- Stage 1 is per-block; as long as every nᵢ is a multiple of the block size m,
  no block straddles a system boundary, so blocks of different systems never
  mix.
- The reduced interface system decouples at every boundary: the first block of
  each system has a zero left spike (``red_dl = 0``) and the last block a zero
  right coupling (``red_du = 0``), so one Thomas sweep passes through each
  boundary with an exact zero elimination weight.
- Stage 3's cross-block term at a boundary is ``v·s_{p-1}`` with ``v = 0``.

The per-system *offset table* (``SolvePlan.offsets``) records where each
solution lives in the fused vector so :func:`split_ragged` can take it apart.
One fused chunked solve therefore covers a heterogeneous batch — mixed-size
serving traffic no longer waits for size-mates (`repro.serve.solve`).

The heuristic prices a ragged batch by its **effective size** ``Σ nᵢ``
(`repro.core.tridiag.plan.effective_size`,
``BatchedStreamHeuristic.predict_optimum_ragged``): the fused solve presents
the device with one ``Σ nᵢ``-element workload, the exact ragged analogue of
the same-size campaign's ``n·B`` feature.

:func:`fuse_ragged` validates every system up front — the four diagonals of a
system must be 1-D and equally long, and a malformed request is rejected with
its batch index. (Silently fusing a short diagonal would shift every
subsequent system's rows and corrupt *all* their solutions, which is fatal in
the serving path where one bad request rides with innocent neighbours.)

API example (the facade is the front door)::

    from repro.api import SolverConfig, TridiagSession

    systems = [(dl1, d1, du1, b1), (dl2, d2, du2, b2)]   # sizes 200 and 5000
    session = TridiagSession(
        SolverConfig(m=10, policy=HeuristicChunkPolicy(heur), backend="pallas")
    )
    xs = session.solve_many(systems)                     # list of solutions
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from repro.core.tridiag.plan import effective_size

__all__ = [
    "effective_size",
    "fuse_ragged",
    "split_ragged",
]

System = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def fuse_ragged(
    systems: Sequence[System],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, Tuple[int, ...]]:
    """Fuse mixed-size systems into one ``(Σ nᵢ,)`` system.

    ``systems`` is a sequence of 1-D ``(dl, d, du, b)`` tuples. Boundary
    couplings (``dl[0]``, ``du[-1]`` of every system) are zeroed — they are
    ignored by convention in the standalone solves, and zeroing them is what
    makes the fused solve decouple exactly (module docstring). Mixed dtypes
    promote via NumPy's usual rules. Returns the four fused arrays plus the
    per-system size tuple consumed by :func:`build_plan`/:func:`split_ragged`.
    """
    if not systems:
        raise ValueError("fuse_ragged needs at least one system")
    dls, ds, dus, bs = [], [], [], []
    sizes: List[int] = []
    for i, (dl, d, du, b) in enumerate(systems):
        dl = np.array(dl, copy=True)
        du = np.array(du, copy=True)
        d = np.asarray(d)
        b = np.asarray(b)
        if d.ndim != 1:
            raise ValueError(
                f"ragged fusion takes 1-D systems, got shape {d.shape}"
            )
        # One short/long diagonal would shift every subsequent system in the
        # fused arrays and silently corrupt all their solutions — reject the
        # offending system by index instead.
        for name, a in (("dl", dl), ("du", du), ("b", b)):
            if a.shape != d.shape:
                raise ValueError(
                    f"system {i}: {name} has shape {a.shape} but d has "
                    f"shape {d.shape}; all four diagonals must be equally long"
                )
        dl[0] = 0.0
        du[-1] = 0.0
        sizes.append(d.shape[0])
        dls.append(dl)
        ds.append(d)
        dus.append(du)
        bs.append(b)
    fused = tuple(np.ascontiguousarray(np.concatenate(p)) for p in (dls, ds, dus, bs))
    return (*fused, tuple(sizes))


def split_ragged(x: np.ndarray, sizes: Sequence[int]) -> List[np.ndarray]:
    """Inverse of :func:`fuse_ragged` for the solution vector."""
    x = np.asarray(x)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    if x.shape[-1] != offsets[-1]:
        raise ValueError(
            f"solution has {x.shape[-1]} rows, sizes sum to {offsets[-1]}"
        )
    return [x[..., lo:hi] for lo, hi in zip(offsets[:-1], offsets[1:])]

