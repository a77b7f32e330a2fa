"""Real wall-clock measurement path: run the chunked JAX partition solver on
THIS machine and feed the same ML pipeline the simulator feeds (DESIGN.md §2.2
— demonstrates the heuristic is hardware-agnostic; on a TPU host the identical
code measures chunked device execution).

All three campaigns time each solve with :func:`staged_timing`: the plan a
session would build for the same sizes and chunk count, run by the staged
:class:`~repro.core.tridiag.plan.PlanExecutor`, whose host round trips
between the stages make the per-phase times (``sum = t1 + t3``, the Eq.-5
overhead) observable.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.streams.simulator import StreamDataset
from repro.core.streams.timemodel import overhead_from_measurement
from repro.core.tridiag.batched import fuse_systems
from repro.core.tridiag.plan import (
    BackendLike,
    ChunkTiming,
    PlanExecutor,
    Sizes,
    build_plan,
)
from repro.core.tridiag.ragged import fuse_ragged
from repro.core.tridiag.reference import make_diag_dominant_system


def staged_timing(
    sizes: Sizes,
    dl: Any,
    d: Any,
    du: Any,
    b: Any,
    *,
    m: int,
    num_chunks: int,
    backend: BackendLike,
) -> Tuple[np.ndarray, ChunkTiming]:
    """One solve of fused operands with its per-phase wall-clock breakdown.

    ``sizes`` is the system size, or the sizes of the systems fused into
    ``dl, d, du, b`` (:func:`~repro.core.tridiag.batched.fuse_systems`,
    :func:`~repro.core.tridiag.ragged.fuse_ragged`). The plan is the one a
    session builds for ``num_chunks`` without a mesh; it runs on the staged
    executor with ``backend`` (None: the reference stages). Returns the
    fused solution and its :class:`ChunkTiming`."""
    plan = build_plan(sizes, m, num_chunks=num_chunks)
    return PlanExecutor(backend).execute(plan, dl, d, du, b)


def _measure_cell(
    rows: List[Dict],
    run: Callable[[int], ChunkTiming],
    *,
    size: int,
    batch: Optional[int],
    candidates: Sequence[int],
    reps: int,
    mix: Optional[Tuple[int, ...]] = None,
) -> None:
    """One campaign cell: profile num_chunks=1, then sweep the candidates.

    ``run(k)`` performs one solve at k chunks and returns its timing. Every
    configuration gets one untimed warmup solve before the timed repeats so
    trace/compile time never lands in the dataset (it used to skew the first
    repeat of small-n rows). The 'sum' of overlappable time is the Stage-1 +
    Stage-3 device time measured at num_chunks=1 (the no-streams profile,
    exactly how the paper measured its Table-1 columns). Both baseline
    quantities — the serial total ``t_non`` and the overlappable ``sum`` —
    come from the single best-total baseline rep: independent minima over
    different reps would mix phases of mismatched runs and could drive the
    Eq.-5 overhead negative."""
    run(1)  # untimed warmup
    base_timings = [run(1) for _ in range(reps)]
    base_best = min(base_timings, key=lambda t: t.t_total_ms)
    t_non = base_best.t_total_ms
    s = base_best.t_stage1_ms + base_best.t_stage3_ms
    for k in candidates:
        if k == 1:
            continue
        run(k)  # untimed warmup (new chunking => new operand shapes)
        for rep in range(reps):
            t = run(k)
            row = dict(
                size=size, num_str=k, rep=rep, sum=s,
                t_str=t.t_total_ms, t_non_str=t_non,
                t_overhead=overhead_from_measurement(t.t_total_ms, t_non, s, k),
                stage_times=None,
            )
            if batch is not None:
                row["batch"] = batch
            if mix is not None:
                row["mix"] = mix
            rows.append(row)


def measure_dataset(
    sizes: Sequence[int],
    candidates: Sequence[int] = (1, 2, 4, 8, 16, 32),
    *,
    m: int = 10,
    reps: int = 3,
    dtype=np.float64,
    seed: int = 0,
    backend=None,
) -> StreamDataset:
    """Wall-clock measurement campaign over (size × num_chunks).

    ``backend`` selects the stage implementation being profiled (reference jnp
    stages by default; ``"pallas"`` measures the kernel path), so one campaign
    pipeline calibrates the heuristic for whichever backend will serve."""
    rows: List[Dict] = []
    for n in sizes:
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=seed, dtype=dtype)
        def run(k, n=n, ops=(dl, d, du, b)):
            return staged_timing(
                n, *ops, m=m, num_chunks=k, backend=backend
            )[1]
        _measure_cell(
            rows, run, size=n, batch=None, candidates=candidates, reps=reps
        )
    return StreamDataset(rows)


def measure_batched_dataset(
    sizes: Sequence[int],
    batches: Sequence[int] = (1, 4, 16),
    candidates: Sequence[int] = (1, 2, 4, 8, 16, 32),
    *,
    m: int = 10,
    reps: int = 3,
    dtype=np.float64,
    seed: int = 0,
    backend=None,
) -> StreamDataset:
    """Wall-clock campaign over the 2-D (size × batch) grid.

    Each cell fuses a batch of B independent size-n systems into one solve
    (on ``backend``); rows carry the ``batch`` key consumed by
    ``fit_batched_stream_heuristic``."""
    rows: List[Dict] = []
    for n in sizes:
        for batch in batches:
            dl, d, du, b, _ = make_diag_dominant_system(
                n, seed=seed, batch=(batch,), dtype=dtype
            )
            ops = fuse_systems(dl, d, du, b)
            def run(k, group=(n,) * batch, ops=ops):
                return staged_timing(
                    group, *ops, m=m, num_chunks=k, backend=backend
                )[1]
            _measure_cell(
                rows, run, size=n, batch=batch, candidates=candidates, reps=reps
            )
    return StreamDataset(rows)


def measure_ragged_dataset(
    mixes: Sequence[Sequence[int]],
    candidates: Sequence[int] = (1, 2, 4, 8, 16, 32),
    *,
    m: int = 10,
    reps: int = 3,
    dtype=np.float64,
    seed: int = 0,
    backend=None,
) -> StreamDataset:
    """Wall-clock campaign over ragged mixed-size batches.

    Each cell fuses one *mix* — a tuple of heterogeneous system sizes — into a
    single solve (on ``backend``) and sweeps the chunk candidates. Rows
    carry ``size = Σ nᵢ`` (the effective size the heuristic prices ragged
    batches by) and the originating ``mix``, so the same
    ``fit_batched_stream_heuristic`` pipeline consumes them unchanged."""
    rows: List[Dict] = []
    for mix in mixes:
        mix = tuple(int(n) for n in mix)
        *ops, fused_sizes = fuse_ragged([
            make_diag_dominant_system(n, seed=seed + i, dtype=dtype)[:4]
            for i, n in enumerate(mix)
        ])
        def run(k, group=fused_sizes, ops=tuple(ops)):
            return staged_timing(
                group, *ops, m=m, num_chunks=k, backend=backend
            )[1]
        _measure_cell(
            rows, run, size=sum(mix), batch=None, candidates=candidates,
            reps=reps, mix=mix,
        )
    return StreamDataset(rows)
