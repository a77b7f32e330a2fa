"""Public front door: ``from repro.api import SolverConfig, TridiagSession``.

Thin re-export of :mod:`repro.core.tridiag.api` — one frozen config naming
the whole solve configuration, one session serving every batch shape
(single, same-size batched, ragged, async with futures). See that module's
docstring and the root README for the full tour.
"""

from repro.core.tridiag.api import (
    AUTOTUNE_MODES,
    BACKEND_NAMES,
    AdmissionPolicy,
    PredictedTimeoutError,
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
from repro.core.tridiag.plan import (
    BACKENDS,
    ChunkPolicy,
    FixedChunkPolicy,
    FusedExecutor,
    HeuristicChunkPolicy,
    PallasBackend,
    PlanExecutor,
    ReferenceBackend,
    StageBackend,
    clear_executable_cache,
    executable_cache_stats,
    plan_cache_stats,
    set_executable_cache_capacity,
)
from repro.telemetry import (
    BatchObservation,
    LatencyModel,
    OnlineRefitter,
    TelemetryBuffer,
)

__all__ = [
    "AUTOTUNE_MODES",
    "AdmissionPolicy",
    "BACKEND_NAMES",
    "BatchObservation",
    "BACKENDS",
    "ChunkPolicy",
    "FixedChunkPolicy",
    "FusedExecutor",
    "HeuristicChunkPolicy",
    "LatencyModel",
    "OnlineRefitter",
    "PallasBackend",
    "PlanExecutor",
    "PredictedTimeoutError",
    "QueueFullError",
    "ReferenceBackend",
    "RequestCancelledError",
    "RequestTimedOutError",
    "ServingError",
    "SolveEngine",
    "SolveFuture",
    "SolveRequest",
    "SolverConfig",
    "StageBackend",
    "TelemetryBuffer",
    "TridiagSession",
    "WorkerDiedError",
    "clear_executable_cache",
    "executable_cache_stats",
    "plan_cache_stats",
    "set_executable_cache_capacity",
]
