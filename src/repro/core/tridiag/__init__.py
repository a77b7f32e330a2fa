"""Tridiagonal solvers: Thomas reference + the paper's parallel partition method.

The partition method (Austin–Berndt–Moulton variant used by the paper) splits an
N-row tridiagonal system into P = N/m sub-systems ("blocks") of m rows:

  Stage 1 (parallel over blocks, GPU in the paper): eliminate each block's
          interior to produce one interface equation per block — a reduced
          tridiagonal system of size P in the block-boundary unknowns
          s_p = x[(p+1)m - 1].
  Stage 2 (serial, CPU in the paper): solve the reduced P-size system.
  Stage 3 (parallel over blocks): back-substitute s into block interiors.

`plan.py` adds the CUDA-stream analogue: the block dimension is split into
`num_chunks` slices ("virtual streams"); the staged ``PlanExecutor`` overlaps
their host staging and device compute via JAX async dispatch (DESIGN.md
§2.1).

Batched solving & autotune
--------------------------
`batched.py` extends the pipeline to many independent systems at once — the
production regime of the ROADMAP north star. A batch of B size-n systems
fuses (by concatenation, with boundary couplings zeroed) into one B·n solve
whose reduced system decouples exactly, so chunks span system boundaries::

    from repro.core.tridiag.batched import solve_batched

    x = solve_batched(dl, d, du, b, m=10)            # (B, n) -> (B, n)

The optimum chunk count over the 2-D (size, batch) grid is fitted/predicted
by ``repro.core.autotune.heuristic.BatchedStreamHeuristic`` (ground truth:
``StreamSimulator.actual_optimum(n, batch=B)``), and served through
``HeuristicChunkPolicy`` by the session below.

The front door: config + session (``api.py``)
---------------------------------------------
`api.py` (re-exported as ``repro.api``) is the ONE public entry point: a
frozen ``SolverConfig`` names the whole solve configuration once (m, dtype,
backend — default ``"auto"``: Pallas kernels on TPU hosts, reference stages
elsewhere — chunk policy, admission and plan-cache knobs, ``validate()``
with actionable errors) and a ``TridiagSession`` built from it serves every
batch shape through four verbs::

    from repro.api import SolverConfig, TridiagSession, SolveRequest

    cfg = SolverConfig(m=10, policy=HeuristicChunkPolicy(h),
                       max_batch=64, max_wait_ms=5.0)
    with TridiagSession(cfg) as s:
        x   = s.solve(dl, d, du, b)          # one system
        xb  = s.solve_batched(DL, D, DU, B)  # (B, n) same-size batch
        xs  = s.solve_many(systems)          # ragged mixed-size batch
        xp  = s.solve_periodic_batched(DL, D, DU, B)  # (B, n) cyclic systems
        fut = s.submit(SolveRequest(0, dl, d, du, b))   # async serving
        x0  = fut.result(timeout=1.0)        # deadline fires w/o poll()

``submit`` is backed by a daemon worker thread running the admission loop
(`api.SolveEngine`); ``close()``/the context manager drains the queue.

Plan/execute architecture
-------------------------
`plan.py` is the single execution path: an immutable ``SolvePlan`` (fused
block layout, chunk bounds, halo map, per-system offsets; chunk count from a
pluggable ``ChunkPolicy``) executed by ``FusedExecutor`` (the whole
three-stage solve AOT-compiled into one donated-buffer executable, cached in
a bounded LRU), the session's only executor. ``PlanExecutor`` (per-chunk
dispatch + host reduced solve, per-phase ``ChunkTiming``) is the paper's
measuring instrument, run by ``repro.core.streams.measure.staged_timing``.
Stage callables are cached module-wide per ``(m, backend)``; the stage
implementation is itself pluggable (``ReferenceBackend`` jnp stages,
``PallasBackend`` kernels, ``"auto"`` resolving per host), and plans are
memoised by their ``(sizes, m, num_chunks)`` signature (all caches
lock-protected: sessions solve from two threads). `ragged.py` fuses
*mixed-size* systems into one block axis (exact decoupling via zeroed
boundary couplings), so one fused chunked solve covers a heterogeneous
batch — priced by its effective size ``Σ nᵢ`` through the stream heuristic.

Operand layouts (``layout.py``)
-------------------------------
Operand layout is a ``StageBackend`` concern, picked by
``SolverConfig.layout``. ``"system-major"`` keeps fused systems concatenated
(the chunk-sliceable order above). ``"interleaved"`` regathers a fused batch
to the lane-major wide form ``(P, m, B)`` — systems on the kernels' minor
(vector-lane) axis — so stage-1/stage-3 tiles work B systems per lane-block
and the Stage-2 reduced solve becomes B *parallel* length-P scans instead of
one serial ``Σ Pᵢ`` scan; ragged batches pad to ``P_max`` blocks with
*exact* identity blocks. Both gathers are traced into the fused executable
(callers and the serving engine never see the transposed layout, and buffer
donation still applies to the caller-visible operands). ``"auto"`` (default)
interleaves wide flat fused batches (B ≥ ``layout.AUTO_INTERLEAVE_MIN_BATCH``
systems, bounded padding waste) and stays system-major otherwise.

Multi-device execution (``SolverConfig.mesh``)
----------------------------------------------
The fused solve shards across a device mesh (``repro.parallel.solver`` owns
the mesh plumbing): ``mesh = None | "auto" | <count> | Mesh | devices``. On
the system-major layout the fused block axis splits over a ``"chunks"`` mesh
axis — plans are built shard-aligned, stage 1/stage 3 run per-shard under
``shard_map`` after a one-block ``ppermute`` halo exchange, and only the
tiny reduced system is gathered (``all_gather`` of per-shard reduced rows,
replicated device Thomas solve). On the interleaved layout the lane axis
splits over a ``"batch"`` axis with no collectives, and the ``"auto"``
interleave threshold counts per-shard lanes. Sharded executables are cached
under the device-set signature, and ``mesh=None`` stays bit-identical to
the single-device build. CPU rigs exercise the whole path under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (``tests/conftest
.py``, ``benchmarks/sharded_throughput.py``).

Checked invariants
------------------
This package's concurrency and donation contracts are machine-checked:
``repro.analysis`` (CI's ``invariants`` job, ``python -m repro.analysis
check src tests``) lexically proves that every access to the plan/executable
LRUs and the engine/session queue state sits under its registered lock
(TRD001), that no device array is reused after being donated to
``FusedExecutor.execute`` (TRD002), and that jitted/Pallas-staged bodies stay
host-effect free (TRD003). Adding a cache, lock, or donating entry point
here means registering it in ``repro/analysis/registry.py``; ``api``,
``plan``, ``layout`` and ``ragged`` are additionally held to
``disallow_untyped_defs`` under mypy (see ``mypy.ini``).
"""

from repro.core.tridiag.thomas import thomas, thomas_factor, thomas_solve_factored
from repro.core.tridiag.partition import (
    PartitionCoeffs,
    partition_solve,
    partition_stage1,
    partition_stage2,
    partition_stage3,
)
from repro.core.tridiag.reference import (
    make_diag_dominant_system,
    thomas_numpy,
    tridiag_matvec,
    tridiag_to_dense,
)
from repro.core.tridiag.layout import (
    AUTO_INTERLEAVE_MIN_BATCH,
    LAYOUTS,
    deinterleave,
    interleave,
    interleave_operands,
    resolve_layout,
)
from repro.core.tridiag.plan import (
    BACKENDS,
    ChunkPolicy,
    ChunkTiming,
    FixedChunkPolicy,
    FusedExecutor,
    HeuristicChunkPolicy,
    PallasBackend,
    PlanExecutor,
    ReferenceBackend,
    SolvePlan,
    StageBackend,
    build_plan,
    clear_executable_cache,
    clear_plan_cache,
    effective_size,
    executable_cache_stats,
    jitted_stage3_ghost,
    jitted_stages,
    jitted_wide_stages,
    plan_cache_stats,
    price_chunks,
    resolve_backend,
    set_executable_cache_capacity,
)
from repro.core.tridiag.batched import (
    fuse_systems,
    solve_batched,
    split_systems,
    thomas_batched,
)
from repro.core.tridiag.ragged import (
    fuse_ragged,
    split_ragged,
)
from repro.core.tridiag.api import (
    AdmissionPolicy,
    QueueFullError,
    RequestCancelledError,
    RequestTimedOutError,
    ServingError,
    SolveEngine,
    SolveFuture,
    SolveRequest,
    SolverConfig,
    TridiagSession,
    WorkerDiedError,
)

__all__ = [
    "thomas",
    "thomas_factor",
    "thomas_solve_factored",
    "PartitionCoeffs",
    "partition_solve",
    "partition_stage1",
    "partition_stage2",
    "partition_stage3",
    "make_diag_dominant_system",
    "thomas_numpy",
    "tridiag_matvec",
    "tridiag_to_dense",
    "BACKENDS",
    "ChunkPolicy",
    "ChunkTiming",
    "FixedChunkPolicy",
    "FusedExecutor",
    "HeuristicChunkPolicy",
    "PallasBackend",
    "PlanExecutor",
    "ReferenceBackend",
    "SolvePlan",
    "StageBackend",
    "build_plan",
    "clear_executable_cache",
    "clear_plan_cache",
    "effective_size",
    "executable_cache_stats",
    "jitted_stage3_ghost",
    "jitted_stages",
    "jitted_wide_stages",
    "AUTO_INTERLEAVE_MIN_BATCH",
    "LAYOUTS",
    "deinterleave",
    "interleave",
    "interleave_operands",
    "resolve_layout",
    "plan_cache_stats",
    "price_chunks",
    "resolve_backend",
    "set_executable_cache_capacity",
    "solve_batched",
    "thomas_batched",
    "fuse_systems",
    "split_systems",
    "fuse_ragged",
    "split_ragged",
    "AdmissionPolicy",
    "QueueFullError",
    "RequestCancelledError",
    "RequestTimedOutError",
    "ServingError",
    "SolveEngine",
    "SolveFuture",
    "SolveRequest",
    "SolverConfig",
    "TridiagSession",
    "WorkerDiedError",
]


def ensure_x64() -> None:
    """Enable float64 support (the paper's FP64 precision) process-wide.

    Kept as an explicit opt-in so the LM stack keeps default f32/bf16 type
    promotion semantics.
    """
    import jax

    jax.config.update("jax_enable_x64", True)
