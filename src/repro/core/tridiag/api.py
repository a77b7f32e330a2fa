"""One front door for the predictive solve pipeline: config → session → verbs.

The paper's deliverable is *predictive*: describe the workload once, let the
fitted heuristic pick the optimum stream count, then run the partition solve.
This module is the API expression of that contract. A frozen
:class:`SolverConfig` names the whole solve configuration exactly once —
sub-system size ``m``, precision, stage backend, chunk policy, admission and
plan-cache knobs — and a :class:`TridiagSession` built from it serves every
batch shape through four verbs:

``solve(dl, d, du, b)``
    one tridiagonal system (1-D diagonals; extra leading dims pass through);
``solve_batched(dl, d, du, b)``
    B same-size systems as ``(B, n)`` operands, fused into one dispatch;
``solve_many(systems)``
    a ragged list of mixed-size systems, fused into one dispatch;
``solve_periodic(dl, d, du, b)`` / ``solve_periodic_batched(dl, d, du, b)``
    one cyclic system, or B same-size ones as ``(B, n)`` operands, where
    ``dl[..., 0]`` couples row 0 to ``x[n-1]`` and ``du[..., n-1]`` couples
    row n-1 to ``x[0]``;
``submit(req) -> SolveFuture``
    asynchronous serving — the request joins the session's admission queue
    and the future resolves when its batch dispatches.

How each verb *executes* is the config's ``dispatch`` knob: ``"staged"``
(per-chunk dispatch + host reduced solve, per-phase timing), ``"fused"``
(the whole three-stage solve compiled into one donated-buffer XLA dispatch,
reduced solve on device), or ``"auto"`` (default) — fused for the plain
verbs and served batches, staged for the ``*_timed`` verbs so the
measurement campaigns keep their phase breakdown.

``submit`` is backed by a daemon worker thread driving the
:class:`AdmissionPolicy` loop, so a deadline (``max_wait_ms``) fires without
anyone calling a ``poll()``: the worker sleeps exactly until the oldest
request's deadline (or a ``max_batch`` wake-up) and dispatches the batch.
``SolveFuture.result(timeout=...)`` blocks; ``.done()`` never does.
``session.close()`` (or leaving the ``with`` block) drains the queue so every
outstanding future completes, then stops the worker; the worker thread is
only started by the first ``submit``, so synchronous-only sessions never pay
for one.

Serving under load (the heavy-traffic contract):

- **No future is ever left unresolved.** Any dispatch failure — in the
  solve itself or anywhere in its tail (splitting, casting, stats, a result
  callback) — fails exactly that batch's futures via ``on_error`` and the
  worker keeps serving; the worker is additionally supervised so that even
  an unexpected escape fails every outstanding future with
  :class:`WorkerDiedError` and the next ``submit`` surfaces the death
  instead of enqueuing into a void.
- **Backpressure.** ``SolverConfig.max_queue`` bounds the admission queue:
  ``submit`` raises :class:`QueueFullError` when full, ``try_submit``
  returns None instead — both immediately, so callers can shed or retry.
- **Deadlines and cancellation.** A :class:`SolveRequest` may carry
  ``timeout_ms`` (shed from the queue with :class:`RequestTimedOutError`
  once expired, before it can poison a batch) and ``priority`` (higher
  admits first; FIFO within a priority). ``SolveFuture.cancel()`` removes a
  still-queued request (:class:`RequestCancelledError`); once its batch is
  taken it runs to completion and ``cancel`` returns False.
- **Observability.** ``session.stats`` is a consistent lock-held snapshot:
  dispatch aggregates, queue depth and high-water mark,
  rejected/timed-out/cancelled/failed counts, and the plan- and
  executable-cache counters.

The queue/admission/dispatch core is :class:`SolveEngine` — the rebuilt
``serve.solve.BatchedSolveService``, which survives there as a thin deprecated
shim over this engine with its legacy ``submit/poll/flush`` contract.

Usage::

    from repro.api import SolverConfig, TridiagSession, SolveRequest

    cfg = SolverConfig(m=10, policy=HeuristicChunkPolicy(fitted),
                       max_batch=64, max_wait_ms=5.0)
    with TridiagSession(cfg) as session:
        x = session.solve(dl, d, du, b)                   # one system
        xs = session.solve_batched(DL, D, DU, B)          # (B, n) batch
        ys = session.solve_many(systems)                  # ragged mix
        fut = session.submit(SolveRequest(0, dl, d, du, b))
        x0 = fut.result(timeout=1.0)                      # deadline-served
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.streams.timemodel import LatencyModel
from repro.core.tridiag import spans
from repro.core.tridiag.batched import fuse_systems, split_systems
from repro.core.tridiag.layout import LAYOUTS, resolve_layout
from repro.core.tridiag.plan import (
    BACKENDS,
    BackendLike,
    ChunkPolicy,
    ChunkTiming,
    FusedExecutor,
    PlanExecutor,
    SolvePlan,
    Sizes,
    build_plan,
    effective_size,
    executable_cache_stats,
    plan_cache_stats,
    price_chunks,
    resolve_backend,
    set_plan_cache_capacity,
)
from repro.core.tridiag.ragged import System, fuse_ragged, split_ragged
from repro.parallel.solver import (
    mesh_signature,
    resolve_mesh_devices,
    shard_count,
)
from repro.telemetry.refit import AUTOTUNE_MODES, OnlineRefitter
from repro.telemetry.ring import BatchObservation, TelemetryBuffer

__all__ = [
    "AUTOTUNE_MODES",
    "AdmissionPolicy",
    "DISPATCH_MODES",
    "LAYOUTS",
    "PredictedTimeoutError",
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


# ------------------------------------------------------------- typed errors --
class ServingError(RuntimeError):
    """Base of the serving layer's typed failures.

    Every subclass is a *flow-control signal*, not a solver bug: callers
    under load are expected to catch these and shed, retry, or re-route.
    """


class QueueFullError(ServingError):
    """``submit`` rejected a request because the admission queue is at
    ``max_queue``. Raised (or signalled as ``try_submit() is None``)
    immediately — the caller should shed the request or retry later; nothing
    was enqueued."""


class RequestTimedOutError(ServingError):
    """A request's ``timeout_ms`` expired while it was still queued; it was
    shed before admission and its future resolves with this error. Work
    already admitted into a batch is never interrupted."""


class RequestCancelledError(ServingError):
    """The request was removed from the queue by ``SolveFuture.cancel()``
    before its batch was taken."""


class PredictedTimeoutError(RequestTimedOutError):
    """Predicted-latency admission shed the request *before* dispatch: the
    active :class:`~repro.core.streams.timemodel.LatencyModel` predicted the
    solve would complete after the request's ``timeout_ms`` deadline, so
    queueing it into a batch could only waste the batch's budget. Subclasses
    :class:`RequestTimedOutError` so deadline-aware callers need no new
    handler; catch this type specifically to distinguish a model-predicted
    shed from an observed queue-wait expiry."""


class WorkerDiedError(ServingError):
    """The session's serving worker terminated abnormally (supervision
    caught an escape it could not attribute to one batch). Every future
    outstanding at death resolves with this error, and subsequent ``submit``
    calls raise it instead of enqueuing into a void — create a new session."""


# ------------------------------------------------------------------ request --
@dataclass
class SolveRequest:
    """One tridiagonal system to solve (the serving unit of work).

    ``timeout_ms`` (optional) is the request's own queue deadline: if it has
    not been admitted into a batch within this many milliseconds of submit,
    it is shed and its future resolves with :class:`RequestTimedOutError`
    (a batch already taken runs to completion). ``priority`` orders
    admission: higher priorities are taken first, FIFO within a priority —
    it never preempts work already in flight.
    """

    rid: int
    dl: np.ndarray
    d: np.ndarray
    du: np.ndarray
    b: np.ndarray
    timeout_ms: Optional[float] = None
    priority: int = 0

    @property
    def size(self) -> int:
        return int(np.asarray(self.d).shape[-1])


@dataclass(frozen=True)
class AdmissionPolicy:
    """When does a batch leave the queue?

    ``max_batch``    dispatch as soon as this many requests are waiting;
    ``max_wait_ms``  dispatch (a possibly partial batch) once the oldest
                     request has waited this long — the session's worker
                     thread sleeps exactly until this deadline, the legacy
                     service checks it on :meth:`SolveEngine.poll`;
    ``allow_ragged`` fuse a mixed-size FIFO prefix into one ragged plan.
                     When False, a batch only takes queue entries matching the
                     head request's size (the PR-1 size-segregated behaviour,
                     kept as the benchmark baseline).
    """

    max_batch: int = 64
    max_wait_ms: float = math.inf
    allow_ragged: bool = True

    def __post_init__(self) -> None:
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_wait_ms < 0:
            raise ValueError("max_wait_ms must be >= 0")


#: Valid ``SolverConfig.dispatch`` values.
DISPATCH_MODES = ("staged", "fused", "auto")


# ------------------------------------------------------------------- config --
@dataclass(frozen=True)
class SolverConfig:
    """The whole solve configuration, named once.

    ``m``          the paper's sub-system (block) size; every system size must
                   be a multiple of it.
    ``dtype``      operand precision. ``None`` (default) preserves the input
                   dtype; an explicit float dtype casts every operand on the
                   way in (``np.float64`` is the paper's precision — remember
                   ``repro.core.tridiag.ensure_x64()``). On a TPU the Pallas
                   kernels take fp32 only: fp64 there is refused with a
                   ``ValueError`` (by :meth:`validate` for ``dtype``, by the
                   first solve for fp64 operands), never downcast.
    ``backend``    stage implementation: ``"auto"`` (default — Pallas kernels
                   on TPU hosts, reference jnp stages elsewhere),
                   ``"reference"``, ``"pallas"``, or a ``StageBackend``.
    ``dispatch``   execution mode: ``"staged"`` (per-chunk dispatch + host
                   reduced solve — the paper's layout, with the per-phase
                   ``ChunkTiming`` breakdown), ``"fused"`` (the whole solve
                   compiled into one donated-buffer XLA dispatch, reduced
                   solve on device — fastest, but phase times are
                   structurally unobservable), or ``"auto"`` (default):
                   fused for the plain verbs and the serving path, staged
                   for the ``*_timed`` verbs so measurement campaigns keep
                   the breakdown the paper's Eq.-5 analysis needs.
    ``layout``     operand layout for the device stages: ``"system-major"``
                   (fused systems stay concatenated; chunk bounds slice the
                   block axis), ``"interleaved"`` (batch-interleaved /
                   lane-major: systems ride the kernels' minor axis and the
                   reduced solve runs B parallel scans — the big win for
                   many-small-system batches), or ``"auto"`` (default):
                   interleave fused dispatches of flat batches at
                   B ≥ ``layout.AUTO_INTERLEAVE_MIN_BATCH`` with bounded
                   ragged padding, system-major otherwise. Layout conversion
                   is traced into the executable — callers never see it.
    ``mesh``       device mesh for sharded fused execution: ``None`` (default
                   — single device, today's path bit for bit), ``"auto"``
                   (shard whenever more than one device is visible), an int
                   device count, a 1-D ``jax.sharding.Mesh``, or an explicit
                   device sequence (see
                   :func:`repro.parallel.solver.resolve_mesh_devices`).
                   Sharded sessions build shard-aligned plans (chunk bounds
                   snapped to shard boundaries) and run stage 1/stage 3
                   per-shard under ``shard_map`` with only the reduced
                   system gathered. Requires a fused dispatch mode: a mesh
                   with ``dispatch="staged"`` is rejected by
                   :meth:`validate`; under ``dispatch="auto"`` the
                   ``*_timed`` verbs keep their staged single-device path
                   (phase timing is structurally per-chunk, not per-shard)
                   while the plain verbs and the serving path shard. On CPU
                   hosts, export
                   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
                   before jax initialises to get an 8-device mesh.
    ``policy``     a :class:`~repro.core.tridiag.plan.ChunkPolicy` pricing
                   each dispatch (e.g. ``HeuristicChunkPolicy(fitted)``), or
                   None to use the fixed ``num_chunks``.
    ``num_chunks`` fixed chunk ("virtual stream") count; mutually exclusive
                   with ``policy``. With neither, solves are unchunked.
    ``max_batch`` / ``max_wait_ms`` / ``allow_ragged``
                   admission knobs for :meth:`TridiagSession.submit`
                   (see :class:`AdmissionPolicy`).
    ``max_queue``  backpressure bound on the admission queue: with this many
                   requests already waiting, ``submit`` raises
                   :class:`QueueFullError` and ``try_submit`` returns None —
                   both immediately, so overload turns into shed load
                   instead of unbounded memory. None (default) = unbounded
                   (the pre-hardening behaviour; fine for trusted callers).
    ``plan_cache_capacity``
                   resize the plan LRU at session construction (None leaves
                   it alone; 0 disables plan memoisation). The cache is
                   deliberately PROCESS-WIDE — plans are pure functions of
                   their signature, so sessions share hits — which means this
                   knob affects every live session and the last-constructed
                   session wins; set it from one place in a deployment.
    ``autotune``   closed-loop refit mode (:mod:`repro.telemetry`): ``"off"``
                   (default — no refitter), ``"shadow"`` (periodically refit
                   the heuristic from serving telemetry but only *report*
                   would-be picks via the ``stats["autotune"]`` agreement
                   counters), or ``"live"`` (additionally swap the session's
                   chunk policy to the refit heuristic, atomically).
    ``telemetry_capacity``
                   bound of the per-batch observation ring
                   (:class:`~repro.telemetry.ring.TelemetryBuffer`); 0
                   disables collection (invalid with autotune enabled).
                   Collection is active iff ``autotune != "off"`` or
                   ``max_predicted_ms`` is set — otherwise the serving hot
                   path records nothing.
    ``refit_min_samples`` / ``refit_interval_s``
                   the refitter's gates: a refit attempt needs at least this
                   many buffered observations AND at least this many seconds
                   since the previous attempt (see
                   :class:`~repro.telemetry.refit.OnlineRefitter`).
    ``max_predicted_ms``
                   predicted-latency admission budget: with a fitted
                   :class:`~repro.core.streams.timemodel.LatencyModel`
                   active, batches are packed only up to this predicted
                   dispatch latency (the rest of the queue waits), and a
                   queued request whose predicted completion would blow its
                   own ``timeout_ms`` deadline is shed *before* dispatch with
                   :class:`PredictedTimeoutError`. None (default) disables
                   predicted admission.

    Frozen: a config can be shared between sessions, stored alongside fitted
    heuristics, and varied with :meth:`replace`. :meth:`validate` checks the
    whole object and raises ``ValueError``/``TypeError`` with actionable
    messages; :class:`TridiagSession` calls it for you.
    """

    m: int = 10
    dtype: Optional[object] = None
    backend: BackendLike = "auto"
    dispatch: str = "auto"
    layout: str = "auto"
    mesh: Any = None
    policy: Optional[ChunkPolicy] = None
    num_chunks: Optional[int] = None
    max_batch: int = 64
    max_wait_ms: float = math.inf
    allow_ragged: bool = True
    max_queue: Optional[int] = None
    plan_cache_capacity: Optional[int] = None
    autotune: str = "off"
    telemetry_capacity: int = 1024
    refit_min_samples: int = 64
    refit_interval_s: float = 30.0
    max_predicted_ms: Optional[float] = None

    # -- validation ----------------------------------------------------------
    def validate(self) -> "SolverConfig":
        """Check every field; raise with an actionable message on the first
        problem. Returns self so ``SolverConfig(...).validate()`` chains."""
        if not isinstance(self.m, (int, np.integer)) or self.m < 2:
            raise ValueError(
                f"m={self.m!r}: the sub-system size must be an int >= 2 "
                f"(the paper uses m=10)"
            )
        if self.dtype is not None:
            try:
                kind = np.dtype(self.dtype).kind
            except TypeError:
                raise ValueError(
                    f"dtype={self.dtype!r} is not a NumPy dtype; pass "
                    f"np.float64, np.float32, or None to preserve input dtypes"
                ) from None
            if kind != "f":
                raise ValueError(
                    f"dtype={self.dtype!r}: the solver runs in floating "
                    f"point; pass np.float64, np.float32, or None"
                )
        backend = resolve_backend(self.backend)  # raises naming the known ones
        if self.dtype is not None:
            backend.check_dtype(self.dtype)
        if self.dispatch not in DISPATCH_MODES:
            raise ValueError(
                f"dispatch={self.dispatch!r}: must be one of "
                f"{sorted(DISPATCH_MODES)} ('auto' = fused solves, staged "
                f"*_timed verbs)"
            )
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"layout={self.layout!r}: must be one of {sorted(LAYOUTS)} "
                f"('auto' = interleaved for wide fused batches, system-major "
                f"otherwise)"
            )
        if self.mesh is not None:
            if self.dispatch == "staged":
                raise ValueError(
                    f"mesh={self.mesh!r} with dispatch='staged': the staged "
                    f"path dispatches chunks from a host loop on one device "
                    f"and cannot shard; use dispatch='fused', or 'auto' "
                    f"(sharded plain verbs, staged single-device *_timed "
                    f"verbs)"
                )
            resolve_mesh_devices(self.mesh)  # raises on a bad spec
        if self.policy is not None:
            if not isinstance(self.policy, ChunkPolicy):
                raise TypeError(
                    f"policy must be a ChunkPolicy (e.g. FixedChunkPolicy, "
                    f"HeuristicChunkPolicy), got {self.policy!r}"
                )
            if self.num_chunks is not None:
                raise ValueError(
                    "pass policy= or num_chunks=, not both: a policy prices "
                    "every dispatch, a fixed num_chunks overrides it"
                )
        if self.num_chunks is not None and self.num_chunks < 1:
            raise ValueError(
                f"num_chunks={self.num_chunks}: must be >= 1 (or None for a "
                f"policy/unchunked solve)"
            )
        if self.max_batch < 1:
            raise ValueError(f"max_batch={self.max_batch}: must be >= 1")
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms={self.max_wait_ms}: must be >= 0 "
                f"(math.inf disables the deadline)"
            )
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue={self.max_queue}: must be >= 1 (None disables "
                f"backpressure — the queue grows without bound)"
            )
        if self.plan_cache_capacity is not None and self.plan_cache_capacity < 0:
            raise ValueError(
                f"plan_cache_capacity={self.plan_cache_capacity}: must be "
                f">= 0 (0 disables plan memoisation, None leaves the "
                f"process-wide default)"
            )
        if self.autotune not in AUTOTUNE_MODES:
            raise ValueError(
                f"autotune={self.autotune!r}: must be one of "
                f"{sorted(AUTOTUNE_MODES)} ('shadow' reports would-be refit "
                f"picks, 'live' swaps them in)"
            )
        if self.telemetry_capacity < 0:
            raise ValueError(
                f"telemetry_capacity={self.telemetry_capacity}: must be "
                f">= 0 (0 disables collection)"
            )
        if self.autotune != "off" and self.telemetry_capacity == 0:
            raise ValueError(
                f"autotune={self.autotune!r} needs telemetry to refit from; "
                f"set telemetry_capacity >= refit_min_samples "
                f"(got telemetry_capacity=0)"
            )
        if self.refit_min_samples < 1:
            raise ValueError(
                f"refit_min_samples={self.refit_min_samples}: must be >= 1"
            )
        if self.refit_interval_s < 0:
            raise ValueError(
                f"refit_interval_s={self.refit_interval_s}: must be >= 0"
            )
        if self.max_predicted_ms is not None and self.max_predicted_ms <= 0:
            raise ValueError(
                f"max_predicted_ms={self.max_predicted_ms}: must be > 0 "
                f"(None disables predicted-latency admission)"
            )
        return self

    # -- derived views -------------------------------------------------------
    def replace(self, **changes: Any) -> "SolverConfig":
        """A copy with ``changes`` applied (e.g. ``cfg.replace(num_chunks=k)``
        inside a chunk sweep)."""
        return dataclasses.replace(self, **changes)

    def admission(self) -> AdmissionPolicy:
        """The admission policy the session's serving queue runs under."""
        return AdmissionPolicy(
            max_batch=self.max_batch,
            max_wait_ms=self.max_wait_ms,
            allow_ragged=self.allow_ragged,
        )


# ------------------------------------------------------------------- future --
class SolveFuture:
    """Handle to one submitted request; resolves when its batch dispatches.

    ``result(timeout=)`` blocks until the solution (or re-raises the dispatch
    error); ``done()`` never blocks; ``exception(timeout=)`` blocks like
    ``result`` but returns the error instead of raising it (None on success).
    ``cancel()`` removes the request from the admission queue if its batch
    has not been taken yet (the future then resolves with
    :class:`RequestCancelledError` and ``cancelled()`` is True); once
    admitted — or already resolved — it returns False and the result stands.
    """

    def __init__(self, rid: int) -> None:
        self.rid = rid
        self._event = threading.Event()
        self._value: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None
        # Wired by the session at submit: rid -> bool (de-queued or not).
        self._cancel_hook: Optional[Callable[[int], bool]] = None

    def done(self) -> bool:
        return self._event.is_set()

    def cancel(self) -> bool:
        """Best-effort cancellation: True iff the request was still queued
        and has now been shed (never raises; never blocks on a solve)."""
        if self._event.is_set() or self._cancel_hook is None:
            return False
        return self._cancel_hook(self.rid)

    def cancelled(self) -> bool:
        return self._event.is_set() and isinstance(
            self._error, RequestCancelledError
        )

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} not solved within {timeout}s; is its "
                f"batch still waiting for admission (max_batch/max_wait_ms)?"
            )
        if self._error is not None:
            raise self._error
        assert self._value is not None  # resolved without error => has a value
        return self._value

    def exception(self, timeout: Optional[float] = None) -> Optional[BaseException]:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not resolved within {timeout}s")
        return self._error

    def _resolve(
        self,
        value: Optional[np.ndarray] = None,
        error: Optional[BaseException] = None,
    ) -> None:
        self._value = value
        self._error = error
        self._event.set()


@dataclass
class _Pending:
    req: SolveRequest
    t_submit: float
    seq: int = 0
    expiry: Optional[float] = None  # absolute clock time; None = no timeout

    @property
    def sort_key(self) -> Tuple[int, int]:
        # Admission order: highest priority first, FIFO within a priority.
        return (-self.req.priority, self.seq)


# ------------------------------------------------------------------- engine --
class SolveEngine:
    """Admission-controlled fused solving of a request queue (the core).

    This is the serving engine behind :meth:`TridiagSession.submit` (driven
    by the session's worker thread) and the legacy
    ``serve.solve.BatchedSolveService`` shim (driven by its caller's
    ``submit/poll/flush``). The engine itself is synchronous and not
    thread-safe — the session serialises access around it.

    Chunk pricing: ``policy`` (a :class:`ChunkPolicy`) prices each dispatch,
    or ``heuristic`` (a fitted ``BatchedStreamHeuristic``) via
    ``plan.price_chunks``, else a fixed ``default_chunks``. All dispatches
    run through the plan/execute layer, whose module-level jit/plan caches
    make per-batch construction free of retracing and replanning.

    ``dispatch`` selects the execution path: ``"auto"`` (default) and
    ``"fused"`` serve each batch as ONE compiled XLA dispatch
    (:class:`~repro.core.tridiag.plan.FusedExecutor` — device-side reduced
    solve, donated buffers); ``"staged"`` keeps the per-chunk host-loop path
    (:class:`~repro.core.tridiag.plan.PlanExecutor`).

    Results surface either through the ``on_result``/``on_error`` callbacks
    (the session's futures) or, with no callbacks, an internal ``{rid: x}``
    store drained by :meth:`poll`/:meth:`flush` (the legacy contract).

    ``clock`` (default ``time.perf_counter``) is injectable so deadline tests
    can drive virtual time; batch latency is always real wall time.

    ``max_queue`` bounds the pending queue (:class:`QueueFullError` on
    submit when full; None = unbounded). Requests carry ``priority``
    (higher admits first, FIFO within) and ``timeout_ms`` (expired entries
    are shed before any batch is taken and fail via ``on_error`` with
    :class:`RequestTimedOutError`; with no ``on_error`` attached — the
    legacy poll/flush contract — timeouts are inert, since that contract
    has no error channel).

    Failure containment: with ``on_error`` attached, *nothing* a dispatch
    does can escape — the solve, the result splitting/casting, stats
    recording, and each ``on_result`` delivery are all guarded, and any
    failure resolves exactly the affected requests via ``on_error`` (see
    :meth:`_dispatch`). Without callbacks, a dispatch error propagates to
    the caller of ``poll``/``flush`` (the legacy shim's contract).

    Stats: ``stats["batches"]/["systems"]/["wall_s"]`` aggregate throughput
    (``systems_per_sec``); ``stats["per_batch"]`` records one dict per
    dispatch with the batch composition, chunk count, solve latency and the
    requests' queue wait times; ``rejected``/``timed_out``/``cancelled``/
    ``failed`` count shed and errored requests and ``queue_high_water`` the
    deepest queue seen; ``layout`` and ``stage2`` count dispatches by the
    operand layout and Stage-2 implementation that ran
    (:meth:`record_dispatch`). The dict is mutated under ``_stats_lock`` —
    concurrent readers should take :meth:`stats_snapshot` instead.
    """

    def __init__(
        self,
        *,
        m: int = 10,
        heuristic: Any = None,
        policy: Optional[ChunkPolicy] = None,
        default_chunks: int = 1,
        admission: Optional[AdmissionPolicy] = None,
        eager: bool = False,
        clock: Callable[[], float] = time.perf_counter,
        backend: BackendLike = None,
        dtype: Any = None,
        dispatch: str = "auto",
        layout: str = "auto",
        mesh: Any = None,
        max_queue: Optional[int] = None,
        on_result: Optional[Callable[[int, np.ndarray], None]] = None,
        on_error: Optional[Callable[[int, BaseException], None]] = None,
        executor: Any = None,
        telemetry: Optional[TelemetryBuffer] = None,
        max_predicted_ms: Optional[float] = None,
    ) -> None:
        if dispatch not in DISPATCH_MODES:
            raise ValueError(
                f"dispatch={dispatch!r}: must be one of {sorted(DISPATCH_MODES)}"
            )
        if layout not in LAYOUTS:
            raise ValueError(
                f"layout={layout!r}: must be one of {sorted(LAYOUTS)}"
            )
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue={max_queue}: must be >= 1 (or None)")
        self.admission = admission if admission is not None else AdmissionPolicy()
        self.max_batch = self.admission.max_batch
        self.max_queue = max_queue
        self.heuristic = heuristic
        self.policy = policy
        self.m = m
        self.default_chunks = default_chunks
        self.dtype = dtype
        self.dispatch = dispatch
        self.layout = layout
        self.mesh_devices = resolve_mesh_devices(mesh) if dispatch != "staged" else None
        self._eager = eager
        self._clock = clock
        # Serving dispatches are plain solves (no phase breakdown consumed),
        # so "auto" resolves to the fused single-dispatch path here; the
        # engine always fuses request operands into fresh host arrays, so
        # buffer donation never consumes a caller's array. ``executor=``
        # overrides the choice — primarily the fault-injection seam for the
        # serving tests and the stress benchmark.
        if executor is not None:
            self._executor = executor
        else:
            self._executor = (
                PlanExecutor(backend=backend, layout=layout)
                if dispatch == "staged"
                else FusedExecutor(
                    backend=backend, layout=layout, mesh=self.mesh_devices
                )
            )
        self._on_result = on_result
        self._on_error = on_error
        # Telemetry is optional and bounded: with no buffer (or capacity 0)
        # the hot path records nothing. The latency model rides behind
        # _stats_lock because the worker swaps it mid-serve (refits) while
        # _dispatch and shed_unmeetable read it.
        self.telemetry = telemetry
        self.max_predicted_ms = max_predicted_ms
        self._latency_model: Optional[LatencyModel] = None
        self._queue: List[_Pending] = []
        self._seq = 0
        self._results: Dict[int, np.ndarray] = {}
        # The queue is serialised by the owner (session lock / single-threaded
        # shim), but stats are ALSO written by _dispatch, which the session
        # runs outside its lock so submits keep flowing during a solve —
        # hence their own lock, shared with stats_snapshot().
        self._stats_lock = threading.Lock()
        self.stats = {
            "batches": 0,
            "systems": 0,
            "wall_s": 0.0,
            "per_batch": [],
            "rejected": 0,
            "timed_out": 0,
            "cancelled": 0,
            "failed": 0,
            "shed_predicted": 0,
            "queue_high_water": 0,
            "layout": {},
            "stage2": {},
            "periodic": 0,
        }

    # -- predicted-latency admission ------------------------------------------
    def set_latency_model(self, model: Optional[LatencyModel]) -> None:
        """Install (or clear) the dispatch-latency predictor the admission
        loop prices batches with — called by the session when a refit lands,
        or directly by tests/benchmarks injecting a known model."""
        with self._stats_lock:
            self._latency_model = model

    def latency_model(self) -> Optional[LatencyModel]:
        with self._stats_lock:
            return self._latency_model

    def predicted_batch_ms(self, sizes: Sequence[int]) -> Optional[float]:
        """Predicted dispatch latency of a batch with composition ``sizes``
        under the current chunk pricing; None while no model is fitted."""
        model = self.latency_model()
        if model is None or not sizes:
            return None
        sizes = tuple(sizes)
        return model.predict_ms(
            effective_size(sizes), self.pick_chunks_ragged(sizes)
        )

    def shed_unmeetable(self, now: Optional[float] = None) -> int:
        """Shed every queued request whose own-deadline is predicted blown:
        ``now + predicted_ms(request alone) > expiry`` means even an
        immediate solo dispatch would finish late, so the request is failed
        *now* with :class:`PredictedTimeoutError` instead of wasting a
        batch's budget. Needs an active latency model, predicted admission
        enabled (``max_predicted_ms``) and an ``on_error`` channel; no-op
        (returns 0) otherwise. Runs before every batch take."""
        if (
            self.max_predicted_ms is None
            or self._on_error is None
            or not self._queue
            or self.latency_model() is None
        ):
            return 0
        now = self._clock() if now is None else now
        live: List[_Pending] = []
        doomed: List[_Pending] = []
        for p in self._queue:
            if p.expiry is None:
                live.append(p)
                continue
            pred = self.predicted_batch_ms((p.req.size,))
            if pred is not None and now + pred / 1e3 > p.expiry:
                doomed.append(p)
            else:
                live.append(p)
        if not doomed:
            return 0
        self._queue = live
        with self._stats_lock:
            self.stats["shed_predicted"] += len(doomed)
            self.stats["timed_out"] += len(doomed)
        for p in doomed:
            err = PredictedTimeoutError(
                f"request {p.req.rid} shed before dispatch: predicted solve "
                f"latency would end past its timeout_ms={p.req.timeout_ms} "
                f"deadline (predicted-latency admission, max_predicted_ms="
                f"{self.max_predicted_ms})"
            )
            try:
                self._on_error(p.req.rid, err)
            except Exception:
                pass  # an error channel that raises must not kill serving
        return len(doomed)

    def _pack_by_budget(
        self, take: List[_Pending]
    ) -> Tuple[List[_Pending], List[_Pending]]:
        """Trim an admitted group to the ``max_predicted_ms`` budget: keep
        the longest prefix whose predicted batch latency fits (always at
        least one request — a solo over-budget request must still dispatch,
        or it would starve). Returns ``(take, deferred)``; deferred entries
        go back to the queue head in admission order."""
        if self.max_predicted_ms is None or len(take) <= 1:
            return take, []
        if self.latency_model() is None:
            return take, []
        kept = len(take)
        while kept > 1:
            pred = self.predicted_batch_ms(
                tuple(p.req.size for p in take[:kept])
            )
            if pred is None or pred <= self.max_predicted_ms:
                break
            kept -= 1
        return take[:kept], take[kept:]

    # -- scheduling ----------------------------------------------------------
    def submit(self, req: SolveRequest) -> None:
        """Validate and enqueue a request; with ``eager=True``, admission
        triggers (a full batch) dispatch inside this call.

        Raises :class:`QueueFullError` when ``max_queue`` requests are
        already waiting (backpressure — nothing is enqueued, the caller
        decides whether to retry or shed)."""
        d = np.asarray(req.d)
        if d.ndim != 1:
            raise ValueError(
                f"request {req.rid}: d must be 1-D, got shape {d.shape} "
                f"(use solve_batched for (B, n) operands)"
            )
        # A mismatched diagonal used to sail through submit and explode later
        # inside the fused dispatch with an opaque shape error — worse, inside
        # a batch of innocent neighbours. Name the offender here instead.
        for name in ("dl", "du", "b"):
            a = np.asarray(getattr(req, name))
            if a.shape != d.shape:
                raise ValueError(
                    f"request {req.rid}: {name} has shape {a.shape} but the "
                    f"request's size is {req.size} (d has shape {d.shape}); "
                    f"all four diagonals must be equally long"
                )
        if req.size % self.m:
            raise ValueError(
                f"request {req.rid}: size {req.size} not divisible by m={self.m}"
            )
        if req.timeout_ms is not None and req.timeout_ms < 0:
            raise ValueError(
                f"request {req.rid}: timeout_ms={req.timeout_ms} must be "
                f">= 0 (or None for no queue deadline)"
            )
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            with self._stats_lock:
                self.stats["rejected"] += 1
            raise QueueFullError(
                f"request {req.rid} rejected: admission queue is full "
                f"({len(self._queue)}/{self.max_queue} waiting); retry later "
                f"or shed (try_submit returns None instead of raising)"
            )
        if self.dtype is not None:
            req = dataclasses.replace(
                req,
                **{
                    name: np.asarray(getattr(req, name), dtype=self.dtype)
                    for name in ("dl", "d", "du", "b")
                },
            )
        now = self._clock()
        self._seq += 1
        pending = _Pending(
            req,
            now,
            seq=self._seq,
            expiry=None if req.timeout_ms is None else now + req.timeout_ms / 1e3,
        )
        # Priority insertion keeps the queue sorted by (-priority, seq), so
        # _take_group's prefix IS the admission order.
        bisect.insort(self._queue, pending, key=lambda p: p.sort_key)
        with self._stats_lock:
            self.stats["queue_high_water"] = max(
                self.stats["queue_high_water"], len(self._queue)
            )
        if self._eager:
            self._admit(self._clock())

    def pending(self) -> int:
        return len(self._queue)

    def cancel(self, rid: int) -> Optional[SolveRequest]:
        """Remove a still-queued request; returns it, or None if no request
        with ``rid`` is waiting (already admitted, resolved, or unknown).
        The caller owns resolving the request's future/consumer."""
        for i, p in enumerate(self._queue):
            if p.req.rid == rid:
                del self._queue[i]
                with self._stats_lock:
                    self.stats["cancelled"] += 1
                return p.req
        return None

    def shed_expired(self, now: Optional[float] = None) -> int:
        """Drop every queued request whose ``timeout_ms`` has expired,
        failing each via ``on_error`` with :class:`RequestTimedOutError`;
        returns how many were shed. Runs automatically before any batch is
        taken, so an expired request never rides (or delays) a dispatch.
        No-op without an ``on_error`` channel (legacy poll/flush contract).
        """
        if self._on_error is None or not self._queue:
            return 0
        now = self._clock() if now is None else now
        live = [p for p in self._queue if p.expiry is None or now < p.expiry]
        shed = len(self._queue) - len(live)
        if not shed:
            return 0
        expired = [p for p in self._queue if not (p.expiry is None or now < p.expiry)]
        self._queue = live
        with self._stats_lock:
            self.stats["timed_out"] += shed
        for p in expired:
            err = RequestTimedOutError(
                f"request {p.req.rid} spent more than its timeout_ms="
                f"{p.req.timeout_ms} in the admission queue and was shed "
                f"before dispatch"
            )
            try:
                self._on_error(p.req.rid, err)
            except Exception:
                pass  # an error channel that raises must not kill serving
        return shed

    def pick_chunks(self, size: int, batch: int) -> int:
        """Chunk count for a same-size (size × batch) dispatch."""
        return self.pick_chunks_ragged((size,) * batch)

    def pick_chunks_ragged(self, sizes: Sequence[int]) -> int:
        """Chunk count for any dispatch, priced by its effective size Σ nᵢ
        (same-size batches are the ``(n,)*B`` special case). Delegates to
        `repro.core.tridiag.plan.price_chunks` — the *same* rule
        `HeuristicChunkPolicy` applies, so a batch gets one chunk count no
        matter which entry point prices it."""
        if self.policy is not None:
            return max(1, int(self.policy.num_chunks(tuple(sizes), self.m)))
        if self.heuristic is None:
            return self.default_chunks
        return price_chunks(self.heuristic, tuple(sizes))

    # -- admission -----------------------------------------------------------
    def _oldest_submit(self) -> float:
        # Priority ordering means queue[0] is the *highest-priority* entry,
        # not the oldest — the admission deadline belongs to the oldest.
        return min(p.t_submit for p in self._queue)

    def seconds_to_deadline(self, now: Optional[float] = None) -> Optional[float]:
        """Seconds until the oldest pending request's deadline expires.

        None when the queue is empty or no deadline is configured; 0.0 when
        it has already expired.
        """
        if not self._queue or math.isinf(self.admission.max_wait_ms):
            return None
        now = self._clock() if now is None else now
        deadline = self._oldest_submit() + self.admission.max_wait_ms / 1e3
        return max(0.0, deadline - now)

    def seconds_to_next_event(self, now: Optional[float] = None) -> Optional[float]:
        """Seconds until the next trigger the worker must service: the
        admission deadline (``max_wait_ms``) or the earliest per-request
        ``timeout_ms`` expiry, whichever comes first. None when neither is
        pending — the worker may then sleep until a submit notification.
        This is exactly how long the session's worker thread may sleep
        before the next poll must run."""
        if not self._queue:
            return None
        now = self._clock() if now is None else now
        ticks: List[float] = []
        if not math.isinf(self.admission.max_wait_ms):
            ticks.append(self._oldest_submit() + self.admission.max_wait_ms / 1e3)
        ticks.extend(p.expiry for p in self._queue if p.expiry is not None)
        if not ticks:
            return None
        return max(0.0, min(ticks) - now)

    def _deadline_expired(self, now: float) -> bool:
        return (
            bool(self._queue)
            and (now - self._oldest_submit()) * 1e3 >= self.admission.max_wait_ms
        )

    def take_due_group(self, now: float) -> Optional[List[_Pending]]:
        """Pop the next admissible batch (max_batch reached or deadline
        expired), or None. Expired-timeout requests are shed first, so they
        neither ride a batch nor hold the deadline open. This is the session
        worker's lock-held step — cheap queue surgery only; the dispatch
        itself runs outside the lock so submits keep flowing (and getting
        exact timestamps) while a batch is in flight."""
        self.shed_expired(now)
        self.shed_unmeetable(now)
        if self._queue and (
            len(self._queue) >= self.admission.max_batch
            or self._deadline_expired(now)
        ):
            return self._take_group()
        return None

    def _admit(self, now: float) -> None:
        """Dispatch while an admission trigger holds (max_batch or deadline)."""
        while True:
            group = self.take_due_group(now)
            if group is None:
                return
            self._dispatch(group, now)

    def _take_group(self) -> List[_Pending]:
        q = self._queue
        if self.admission.allow_ragged:
            take, rest = q[: self.max_batch], q[self.max_batch :]
            # Predicted-latency packing: the deferred suffix of the take is a
            # contiguous run of the sorted queue, so prepending it to the
            # rest preserves admission order exactly.
            take, deferred = self._pack_by_budget(take)
            self._queue = deferred + rest
            return take
        # Size-segregated baseline: only the head request's size-mates ride.
        size0 = q[0].req.size
        take, rest = [], []
        for p in q:
            if p.req.size == size0 and len(take) < self.max_batch:
                take.append(p)
            else:
                rest.append(p)
        take, deferred = self._pack_by_budget(take)
        for p in deferred:
            bisect.insort(rest, p, key=lambda p: p.sort_key)
        self._queue = rest
        return take

    def poll(self, now: Optional[float] = None) -> Dict[int, np.ndarray]:
        """Run deadline admission and drain finished results."""
        now = self._clock() if now is None else now
        self._admit(now)
        return self._drain()

    def flush(self) -> Dict[int, np.ndarray]:
        """Dispatch everything pending; returns every undrained {rid: solution}."""
        now = self._clock()
        self.shed_expired(now)
        while self._queue:
            self._dispatch(self._take_group(), now)
        return self._drain()

    # -- execution -----------------------------------------------------------
    def plan_shards(self, sizes: Sequence[int]) -> int:
        """Shard count for a batch's plan: the largest divisor of the fused
        block axis within the mesh's device budget, or 1 without a mesh.
        Shard-aligned plans are harmless on the unsharded/staged paths, so
        one plan serves every executor this engine may route to."""
        if self.mesh_devices is None:
            return 1
        num_blocks = effective_size(tuple(sizes)) // self.m
        return shard_count(num_blocks, len(self.mesh_devices))

    def _drain(self) -> Dict[int, np.ndarray]:
        out, self._results = self._results, {}
        return out

    def _fail_group(self, reqs: Sequence[SolveRequest], e: BaseException) -> None:
        """Fail every request in ``reqs`` via ``on_error`` (each delivery
        guarded — a raising error channel must not take the others down);
        re-raise when there is no error channel (legacy poll/flush)."""
        with self._stats_lock:
            self.stats["failed"] += len(reqs)
        if self._on_error is None:
            raise e
        for r in reqs:
            try:
                self._on_error(r.rid, e)
            except Exception:
                pass

    def _dispatch(self, group: List[_Pending], now: float) -> None:
        """Solve one admitted batch and deliver its results.

        EVERYTHING in here is guarded: the solve, the tail (the
        ``split_ragged`` views, the per-solution cast, stats recording) and
        each per-request delivery. A failure anywhere fails exactly the
        affected requests via ``on_error`` and returns normally — this
        method must never raise into the session's worker loop, because a
        dead worker would hang every pending and future submit (the original
        serving bug: only the solve was guarded, so a post-execute error
        silently killed the daemon thread).
        """
        reqs = [p.req for p in group]
        with jax.profiler.TraceAnnotation(
            spans.BATCH, systems=len(reqs), first_rid=reqs[0].rid if reqs else -1
        ):
            t0 = time.perf_counter()
            try:
                sizes = tuple(r.size for r in reqs)
                same_size = len(set(sizes)) == 1
                dl, d, du, b, sizes = fuse_ragged([(r.dl, r.d, r.du, r.b) for r in reqs])
                # One read of the policy: a live-mode refit swaps it between
                # dispatches, and this batch must be priced (and recorded) by
                # exactly one of the two.
                policy = self.policy
                shards = self.plan_shards(sizes)
                if policy is not None:
                    plan = build_plan(sizes, self.m, policy=policy, shards=shards)
                else:
                    plan = build_plan(
                        sizes,
                        self.m,
                        num_chunks=self.pick_chunks_ragged(sizes),
                        shards=shards,
                    )
                model = self.latency_model()
                predicted_ms = (
                    None
                    if model is None
                    else model.predict_ms(effective_size(sizes), plan.num_chunks)
                )
                x, timing = self._executor.execute(plan, dl, d, du, b)
                self.record_dispatch(timing)
                # copy: split_ragged returns views, which would otherwise pin the
                # whole fused solution for as long as any one result is retained
                solutions = [
                    np.array(xi, dtype=self.dtype, copy=True)
                    for xi in split_ragged(x, sizes)
                ]
                dt = time.perf_counter() - t0
                waits_ms = [(now - p.t_submit) * 1e3 for p in group]
                # Stats are recorded BEFORE futures resolve: a caller unblocked
                # by fut.result() may immediately read session.stats and must see
                # this batch's entry (the worker races it otherwise).
                with self._stats_lock:
                    self.stats["batches"] += 1
                    self.stats["systems"] += len(reqs)
                    self.stats["wall_s"] += dt
                    self.stats["per_batch"].append(
                        {
                            "systems": len(reqs),
                            "sizes": sizes,
                            "effective_size": effective_size(sizes),
                            "ragged": not same_size,
                            "num_chunks": plan.num_chunks,
                            "latency_ms": dt * 1e3,
                            "mean_wait_ms": float(np.mean(waits_ms)),
                            "max_wait_ms": float(np.max(waits_ms)),
                        }
                    )
                if self.telemetry is not None and self.telemetry.enabled:
                    # Guarded separately: telemetry is observability, and a
                    # recording failure must not fail a *solved* batch.
                    try:
                        self.telemetry.record(
                            BatchObservation(
                                t=now,
                                sizes=sizes,
                                num_chunks=plan.num_chunks,
                                backend=str(
                                    getattr(
                                        getattr(self._executor, "backend", None),
                                        "name",
                                        "?",
                                    )
                                ),
                                layout=resolve_layout(
                                    self.layout,
                                    sizes,
                                    self.m,
                                    fused=self.dispatch != "staged",
                                    batch_shards=(
                                        shard_count(len(sizes), len(self.mesh_devices))
                                        if self.mesh_devices is not None
                                        else 1
                                    ),
                                ),
                                dispatch=(
                                    "staged" if self.dispatch == "staged" else "fused"
                                ),
                                latency_ms=dt * 1e3,
                                mean_wait_ms=float(np.mean(waits_ms)),
                                max_wait_ms=float(np.max(waits_ms)),
                                predicted_ms=predicted_ms,
                            )
                        )
                    except Exception:
                        pass
            except Exception as e:
                # A bad dispatch fails *these* requests and leaves the engine
                # serving; the legacy shim (no on_error) keeps the raise.
                self._fail_group(reqs, e)
                return
            for r, xi in zip(reqs, solutions):
                if self._on_result is not None:
                    try:
                        self._on_result(r.rid, xi)
                    except Exception as e:
                        # A result channel that raises fails only ITS request;
                        # the rest of the batch still delivers.
                        self._fail_group([r], e)
                else:
                    self._results[r.rid] = xi

    def record_dispatch(self, timing: ChunkTiming) -> None:
        """Count the layout and Stage-2 implementation one dispatch ran, and
        whether it solved periodic systems."""
        with self._stats_lock:
            for key in ("layout", "stage2"):
                name = getattr(timing, key)
                self.stats[key][name] = self.stats[key].get(name, 0) + 1
            self.stats["periodic"] += int(timing.periodic)

    def stats_snapshot(self) -> dict:
        """A consistent copy of :attr:`stats` (``per_batch`` entries
        included) plus the instantaneous ``queue_depth``, safe to read while
        a dispatch records its batch on another thread."""
        with self._stats_lock:
            snap = {
                k: (
                    [dict(pb) for pb in v]
                    if isinstance(v, list)
                    else dict(v) if isinstance(v, dict) else v
                )
                for k, v in self.stats.items()
            }
        snap["queue_depth"] = len(self._queue)
        return snap

    @property
    def systems_per_sec(self) -> float:
        with self._stats_lock:
            return self.stats["systems"] / max(self.stats["wall_s"], 1e-12)


# ------------------------------------------------------------------ session --
class TridiagSession:
    """The facade: one configured object serving every batch shape.

    Synchronous verbs (:meth:`solve`, :meth:`solve_batched`,
    :meth:`solve_many` and their ``*_timed`` variants) run on the caller's
    thread through the plan/execute layer. :meth:`submit` is asynchronous: a
    daemon worker thread drives the admission loop, so ``max_wait_ms``
    deadlines fire on time without any polling. Both sides share the
    module-level plan/stage caches (lock-protected for exactly this reason),
    so a session is safe to use from the submitting thread while its worker
    dispatches.

    Lifecycle: the worker starts lazily on the first ``submit``;
    :meth:`close` drains the queue (every outstanding future completes) and
    stops the worker; ``close`` is idempotent and ``submit`` after it raises.
    The session is a context manager — ``with TridiagSession(cfg) as s: ...``
    closes on exit.
    """

    def __init__(
        self,
        config: Optional[SolverConfig] = None,
        *,
        refitter: Optional[OnlineRefitter] = None,
    ) -> None:
        self.config = (SolverConfig() if config is None else config).validate()
        self.backend = resolve_backend(self.config.backend)
        # Resolved once: every executor, plan and stats report sees the same
        # device set even if jax's visible devices change later.
        self._mesh_devices = resolve_mesh_devices(self.config.mesh)
        self._executor = PlanExecutor(backend=self.backend, layout=self.config.layout)
        self._fused = FusedExecutor(
            backend=self.backend,
            layout=self.config.layout,
            mesh=self._mesh_devices,
        )
        if self.config.plan_cache_capacity is not None:
            set_plan_cache_capacity(self.config.plan_cache_capacity)
        # RLock-backed so _resolve_future can take it from paths that
        # already hold it (the serve loop's failure drain).
        self._cv = threading.Condition(threading.RLock())
        self._futures: Dict[int, SolveFuture] = {}
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        self._worker_error: Optional[BaseException] = None
        # Closed-loop autotune plumbing. Telemetry collection is on iff
        # something consumes it (a refitter, or predicted admission); the
        # buffer stays capacity-0 otherwise so the hot path records nothing.
        # ``refitter=`` injects a pre-built refitter (typically with a fake
        # clock — the deterministic-test seam); the config builds one
        # whenever ``autotune != "off"``.
        telemetry_on = (
            self.config.autotune != "off"
            or self.config.max_predicted_ms is not None
        )
        self._telemetry = TelemetryBuffer(
            capacity=self.config.telemetry_capacity if telemetry_on else 0
        )
        if refitter is not None:
            self._refitter: Optional[OnlineRefitter] = refitter
        elif self.config.autotune != "off":
            self._refitter = OnlineRefitter(
                mode=self.config.autotune,
                min_samples=self.config.refit_min_samples,
                interval_s=self.config.refit_interval_s,
            )
        else:
            self._refitter = None
        # The chunk policy currently pricing dispatches: starts as the
        # config's, swapped (under _cv) by a live-mode refit. plan_for and
        # the engine read this, never config.policy directly.
        self._active_policy = self.config.policy
        self._engine = SolveEngine(
            m=self.config.m,
            policy=self.config.policy,
            default_chunks=self.config.num_chunks or 1,
            admission=self.config.admission(),
            eager=False,  # the worker owns every dispatch
            backend=self.backend,
            dtype=self.config.dtype,
            dispatch=self.config.dispatch,
            layout=self.config.layout,
            mesh=self._mesh_devices,
            max_queue=self.config.max_queue,
            on_result=lambda rid, x: self._resolve_future(rid, value=x),
            on_error=lambda rid, e: self._resolve_future(rid, error=e),
            telemetry=self._telemetry,
            max_predicted_ms=self.config.max_predicted_ms,
        )

    # -- planning ------------------------------------------------------------
    def plan_for(self, sizes: Sizes, periodic: bool = False) -> SolvePlan:
        """The plan this session executes for ``sizes`` (int or sequence).

        Priced by the *active* chunk policy — the config's, until a
        live-mode refit swaps in the telemetry-fitted one. With a mesh
        configured, plans are shard-aligned (chunk bounds snapped to shard
        boundaries); the staged ``*_timed`` path runs the same plan on one
        device, so both executors agree on the chunk layout. ``periodic``
        plans (cyclic systems) are neither chunked nor sharded."""
        if periodic:
            return build_plan(sizes, self.config.m, periodic=True)
        with self._cv:
            policy = self._active_policy
        shards = self._plan_shards(sizes)
        if policy is not None:
            return build_plan(sizes, self.config.m, policy=policy, shards=shards)
        return build_plan(
            sizes,
            self.config.m,
            num_chunks=self.config.num_chunks or 1,
            shards=shards,
        )

    def _plan_shards(self, sizes: Sizes) -> int:
        """Shard count for this session's plans (1 without a mesh)."""
        if self._mesh_devices is None:
            return 1
        num_blocks = effective_size(sizes) // self.config.m
        return shard_count(num_blocks, len(self._mesh_devices))

    def _cast(self, *arrays: Any) -> Tuple[Any, ...]:
        if self.config.dtype is None:
            return arrays
        return tuple(np.asarray(a, dtype=self.config.dtype) for a in arrays)

    def _cast_out(self, x: Any) -> np.ndarray:
        # The config names the precision once — outputs honour it too (the
        # reference stages may promote fp32 coefficients against the fp64
        # host reduced solve).
        if self.config.dtype is None:
            return x
        return np.asarray(x, dtype=self.config.dtype)

    def _pick_executor(self, timed: bool) -> "PlanExecutor | FusedExecutor":
        """``dispatch`` routing: "staged"/"fused" are unconditional; "auto"
        fuses plain solves but keeps the ``*_timed`` verbs on the staged path,
        whose host round-trips are what make the per-phase ``ChunkTiming``
        (the paper's Eq.-5 decomposition) observable."""
        mode = self.config.dispatch
        if mode == "fused" or (mode == "auto" and not timed):
            return self._fused
        return self._executor

    # -- synchronous verbs ---------------------------------------------------
    def solve(self, dl: Any, d: Any, du: Any, b: Any) -> np.ndarray:
        """Solve one system (1-D diagonals; leading batch dims pass through).

        Under ``dispatch="auto"``/``"fused"`` this is one compiled XLA
        dispatch with donated operand buffers: numpy operands are always safe
        to reuse (copied to device per call), but *device* arrays are
        consumed by the solve — pass fresh ones, or use dispatch="staged".
        """
        return self._solve(dl, d, du, b, timed=False)[0]

    def solve_timed(
        self, dl: Any, d: Any, du: Any, b: Any
    ) -> Tuple[np.ndarray, ChunkTiming]:
        return self._solve(dl, d, du, b, timed=True)

    def _execute(
        self,
        sizes: Sizes,
        timed: bool,
        dl: Any,
        d: Any,
        du: Any,
        b: Any,
        periodic: bool = False,
    ) -> Tuple[Any, ChunkTiming]:
        """Plan the fused operands of one verb call and run them. Periodic
        plans always take the fused executor: the staged path's chunks and
        host reduced solve do not wrap."""
        with jax.profiler.TraceAnnotation(spans.LOOKUP):
            plan = self.plan_for(sizes, periodic)
        executor = self._fused if periodic else self._pick_executor(timed)
        x, timing = executor.execute(plan, dl, d, du, b)
        self._engine.record_dispatch(timing)
        return x, timing

    def _solve(
        self, dl: Any, d: Any, du: Any, b: Any, *, timed: bool
    ) -> Tuple[np.ndarray, ChunkTiming]:
        shape = np.shape(d)
        systems = math.prod(shape[:-1])
        with jax.profiler.TraceAnnotation(
            spans.SOLVE, rows=systems * shape[-1], systems=systems
        ):
            with jax.profiler.TraceAnnotation(spans.FUSE):
                dl, d, du, b = self._cast(dl, d, du, b)
            x, timing = self._execute(int(shape[-1]), timed, dl, d, du, b)
            with jax.profiler.TraceAnnotation(spans.SPLIT):
                return self._cast_out(x), timing

    def solve_batched(self, dl: Any, d: Any, du: Any, b: Any) -> np.ndarray:
        """Solve B same-size systems given as (B, n) operands."""
        return self._solve_batched(dl, d, du, b, timed=False)[0]

    def solve_batched_timed(
        self, dl: Any, d: Any, du: Any, b: Any
    ) -> Tuple[np.ndarray, ChunkTiming]:
        return self._solve_batched(dl, d, du, b, timed=True)

    def _solve_batched(
        self, dl: Any, d: Any, du: Any, b: Any, *, timed: bool
    ) -> Tuple[np.ndarray, ChunkTiming]:
        if np.ndim(d) != 2:
            raise ValueError(
                f"solve_batched takes (batch, n) operands, got shape "
                f"{np.shape(d)}; use solve() for one system or solve_many() "
                f"for mixed sizes"
            )
        batch, n = np.shape(d)
        with jax.profiler.TraceAnnotation(
            spans.SOLVE_BATCHED, rows=batch * n, systems=batch
        ):
            with jax.profiler.TraceAnnotation(spans.FUSE):
                dl, d, du, b = self._cast(dl, d, du, b)
                fused = fuse_systems(dl, d, du, b)
            x, timing = self._execute((n,) * batch, timed, *fused)
            with jax.profiler.TraceAnnotation(spans.SPLIT):
                return split_systems(self._cast_out(x), batch), timing

    def solve_periodic(self, dl: Any, d: Any, du: Any, b: Any) -> np.ndarray:
        """Solve one periodic (cyclic) tridiagonal system of 1-D diagonals.

        Row i reads ``dl[i] x[i-1] + d[i] x[i] + du[i] x[i+1] = b[i]`` with
        the indices taken modulo n: ``dl[0]`` is the coefficient of
        ``x[n-1]`` in row 0, and ``du[n-1]`` the coefficient of ``x[0]`` in
        row n-1 (the non-periodic verbs ignore both). n must be a multiple
        of the config's ``m``. The system runs as a batch of one,
        :meth:`solve_periodic_batched`."""
        if np.ndim(d) != 1:
            raise ValueError(
                f"solve_periodic takes 1-D operands, got shape {np.shape(d)}; "
                f"use solve_periodic_batched() for (batch, n) operands"
            )
        ops = [a[None] if isinstance(a, jax.Array) else np.asarray(a)[None]
               for a in (dl, d, du, b)]
        return self._solve_periodic(*ops, span=spans.SOLVE_PERIODIC)[0]

    def solve_periodic_batched(self, dl: Any, d: Any, du: Any, b: Any) -> np.ndarray:
        """Solve B same-size periodic (cyclic) systems given as (B, n)
        operands, with the corner convention of :meth:`solve_periodic`.

        The systems are never fused end to end, as :meth:`solve_batched`
        fuses them, since a corner would couple neighbours: each keeps its
        wrapped block axis, on the interleaved layout where the config's
        ``layout`` resolves to it for the batch, else on the batched
        ``(B, P)`` kernels. n must be a multiple of ``m``."""
        if np.ndim(d) != 2:
            raise ValueError(
                f"solve_periodic_batched takes (batch, n) operands, got shape "
                f"{np.shape(d)}; use solve_periodic() for one system"
            )
        return self._solve_periodic(dl, d, du, b, span=spans.SOLVE_PERIODIC_BATCHED)

    def _solve_periodic(
        self, dl: Any, d: Any, du: Any, b: Any, *, span: str
    ) -> np.ndarray:
        batch, n = np.shape(d)
        m = self.config.m
        if n % m:
            raise ValueError(
                f"a periodic system of {n} rows needs n % m == 0 (m={m}): the "
                f"identity rows that pad a system to whole blocks would break "
                f"its wrap-around"
            )
        with jax.profiler.TraceAnnotation(span, rows=batch * n, systems=batch):
            with jax.profiler.TraceAnnotation(spans.FUSE):
                # A reshape, not fuse_systems: the corners must survive.
                ops = [
                    a.reshape(-1)
                    if isinstance(a, jax.Array)
                    else np.ascontiguousarray(np.reshape(a, -1))
                    for a in self._cast(dl, d, du, b)
                ]
            x, _ = self._execute((n,) * batch, False, *ops, periodic=True)
            with jax.profiler.TraceAnnotation(spans.SPLIT):
                return split_systems(self._cast_out(x), batch)

    def solve_many(self, systems: Sequence[System]) -> List[np.ndarray]:
        """Solve a ragged list of ``(dl, d, du, b)`` systems in one dispatch."""
        return self._solve_many(systems, timed=False)[0]

    def solve_many_timed(
        self, systems: Sequence[System]
    ) -> Tuple[List[np.ndarray], ChunkTiming]:
        return self._solve_many(systems, timed=True)

    def _solve_many(
        self, systems: Sequence[System], *, timed: bool
    ) -> Tuple[List[np.ndarray], ChunkTiming]:
        with jax.profiler.TraceAnnotation(spans.SOLVE_MANY) as span:
            with jax.profiler.TraceAnnotation(spans.FUSE):
                if self.config.dtype is not None:
                    systems = [self._cast(*s) for s in systems]
                dl, d, du, b, sizes = fuse_ragged(systems)
            span.set_metadata(rows=sum(sizes), systems=len(sizes))
            x, timing = self._execute(sizes, timed, dl, d, du, b)
            with jax.profiler.TraceAnnotation(spans.SPLIT):
                return split_ragged(self._cast_out(x), sizes), timing

    # -- asynchronous serving ------------------------------------------------
    def submit(self, req: SolveRequest) -> SolveFuture:
        """Enqueue a request; the returned future resolves when its batch
        dispatches (at ``max_batch`` occupancy or the ``max_wait_ms``
        deadline — whichever the worker hits first).

        Raises :class:`QueueFullError` when ``SolverConfig.max_queue``
        requests are already waiting (see :meth:`try_submit` for the
        non-raising variant) and :class:`WorkerDiedError` if the serving
        worker has terminated abnormally."""
        return self._submit(req, raise_on_full=True)

    def try_submit(self, req: SolveRequest) -> Optional[SolveFuture]:
        """Like :meth:`submit`, but backpressure-friendly: returns None
        (immediately, nothing enqueued) instead of raising
        :class:`QueueFullError` when the admission queue is full. Every
        other submit failure still raises."""
        return self._submit(req, raise_on_full=False)

    def _submit(self, req: SolveRequest, *, raise_on_full: bool) -> Optional[SolveFuture]:
        fut = SolveFuture(req.rid)
        with self._cv:
            if self._closed:
                raise RuntimeError(
                    "session is closed; create a new TridiagSession (close() "
                    "drains the queue, it cannot be reopened)"
                )
            # A silently-dead worker is the difference between "slow" and
            # "hangs forever": every enqueued request would wait on a thread
            # that no longer exists. Surface the death instead.
            if self._worker_error is not None or (
                self._worker is not None and not self._worker.is_alive()
            ):
                raise WorkerDiedError(
                    f"the serving worker of this session died "
                    f"({self._worker_error!r}); its futures were failed — "
                    f"create a new TridiagSession"
                ) from self._worker_error
            if req.rid in self._futures:
                raise ValueError(
                    f"request id {req.rid} is already in flight in this "
                    f"session; rids must be unique among pending requests"
                )
            self._futures[req.rid] = fut
            try:
                self._engine.submit(req)
            except QueueFullError:
                del self._futures[req.rid]
                if raise_on_full:
                    raise
                return None
            except Exception:
                del self._futures[req.rid]
                raise
            fut._cancel_hook = self._cancel
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._serve_loop,
                    name="tridiag-session-worker",
                    daemon=True,
                )
                self._worker.start()
            self._cv.notify_all()
        return fut

    def _cancel(self, rid: int) -> bool:
        """``SolveFuture.cancel`` hook: shed a still-queued request."""
        with self._cv:
            req = self._engine.cancel(rid)
            if req is None:
                return False  # already admitted (in flight) or resolved
        self._resolve_future(
            rid,
            error=RequestCancelledError(
                f"request {rid} was cancelled while queued (its batch had "
                f"not been taken)"
            ),
        )
        return True

    def _resolve_future(
        self,
        rid: int,
        value: Optional[np.ndarray] = None,
        error: Optional[BaseException] = None,
    ) -> None:
        # Called both with and without _cv held (the serve loop's failure
        # path resolves under the lock) — _cv wraps an RLock so this nests.
        with self._cv:
            fut = self._futures.pop(rid, None)
        if fut is not None:
            fut._resolve(value, error)

    # -- closed-loop autotune ------------------------------------------------
    @property
    def telemetry(self) -> TelemetryBuffer:
        """The session's per-batch observation ring (capacity 0 — recording
        nothing — unless ``autotune`` or ``max_predicted_ms`` enabled it).
        ``snapshot()`` / ``export_jsonl()`` are safe while serving."""
        return self._telemetry

    def _refit_wait_s(self) -> Optional[float]:
        """How long the idle worker may sleep before the next refit could
        fire (None: no refitter, or not enough observations yet — a future
        dispatch will wake the worker anyway)."""
        if self._refitter is None:
            return None
        return self._refitter.seconds_until_due(len(self._telemetry))

    def _maybe_refit(self) -> None:
        """One idle-time refit step. Runs on the worker thread between
        dispatches (and is directly callable from deterministic tests): asks
        the refitter to refit if due, then applies the result — the latency
        model always (it serves predicted admission in every mode), the
        chunk policy only when the refitter produced one (live mode),
        swapped under the session lock so ``plan_for`` and the engine see
        old-or-new, never half."""
        if self._refitter is None:
            return
        result = self._refitter.maybe_refit(
            self._telemetry, pick_active=self._engine.pick_chunks_ragged
        )
        if result is None:
            return
        if result.latency_model is not None:
            self._engine.set_latency_model(result.latency_model)
        if result.policy is not None:
            with self._cv:
                self._active_policy = result.policy
                self._engine.policy = result.policy

    def _serve_loop(self) -> None:
        """Worker: dispatch due batches, sleep exactly until the next trigger.

        Wake-ups: a submit notification (max_batch may now hold), the oldest
        request's admission deadline or the earliest per-request timeout
        (timed wait), or close(). No caller ever polls. The lock is held
        only for queue surgery — each solve runs OUTSIDE it, so submits keep
        enqueuing (with exact deadline timestamps) while a batch is in
        flight.

        Supervision: :meth:`SolveEngine._dispatch` already guards everything
        it does, so per-batch failures resolve that batch's futures and the
        loop keeps serving. The belt-and-braces layers here exist for what
        cannot be attributed to one batch: an in-flight escape still fails
        that group's futures, and an escape from the lock-held queue surgery
        itself (or a non-``Exception`` like ``MemoryError``) fails EVERY
        outstanding future with :class:`WorkerDiedError` before the thread
        exits — no submitted request is ever left unresolved, and the next
        ``submit`` raises instead of enqueuing into a void.
        """
        try:
            while True:
                # Refits run on the worker's idle time, OUTSIDE the lock —
                # the fit is the expensive part and submits must keep
                # flowing through it.
                self._maybe_refit()
                with self._cv:
                    now = self._engine._clock()
                    group = self._engine.take_due_group(now)
                    if group is None:
                        if self._closed:
                            self._engine.shed_expired(now)
                            if self._engine.pending() == 0:
                                return
                            group = self._engine._take_group()  # drain mode
                        elif self._engine.pending() == 0:
                            self._cv.wait(timeout=self._refit_wait_s())
                            continue
                        else:
                            ticks = [
                                t
                                for t in (
                                    self._engine.seconds_to_next_event(now),
                                    self._refit_wait_s(),
                                )
                                if t is not None
                            ]
                            self._cv.wait(
                                timeout=min(ticks) if ticks else None
                            )
                            continue
                try:
                    self._engine._dispatch(group, now)  # futures resolve in here
                except BaseException as e:
                    for p in group:
                        self._resolve_future(p.req.rid, error=e)
                    if not isinstance(e, Exception):
                        raise  # fatal (MemoryError & co) → outer supervisor
        except BaseException as e:
            with self._cv:
                self._worker_error = e
                died = WorkerDiedError(
                    f"serving worker died: {e!r}; this session can no longer "
                    f"serve submits"
                )
                died.__cause__ = e
                self._engine._queue.clear()  # their futures fail right here
                for rid in list(self._futures):
                    self._resolve_future(rid, error=died)
                self._cv.notify_all()

    # -- lifecycle -----------------------------------------------------------
    def pending(self) -> int:
        """Unresolved requests: still queued for admission OR taken into an
        in-flight batch whose futures have not resolved yet. (Counted from
        the futures table — the engine's queue length alone would miss an
        in-flight batch.)"""
        with self._cv:
            return len(self._futures)

    @property
    def stats(self) -> dict:
        """A consistent snapshot of the serving state, taken under the
        session lock — never the live dict the worker mutates.

        Keys: the :class:`SolveEngine` dispatch aggregates (``batches``,
        ``systems``, ``wall_s``, ``per_batch``), the load-shedding counters
        (``rejected``, ``timed_out``, ``cancelled``, ``failed``), queue
        occupancy (``queue_depth``, ``queue_high_water``, ``unresolved`` =
        :meth:`pending`), the process-wide ``plan_cache`` /
        ``executable_cache`` hit/miss counters from
        :mod:`repro.core.tridiag.plan`, and the closed-loop ``autotune``
        block — refit attempts/runs/errors, last-refit age, the
        shadow-vs-live pick agreement counters, and the telemetry ring's
        recorded/dropped/buffered observation counts. ``mesh`` reports the
        active device mesh (None on the single-device path; otherwise the
        device count, platform and device-id signature sharded executables
        run under). ``backend`` names the resolved stage backend and whether
        its kernels run interpreted (None for a backend without kernels);
        ``layout`` and ``stage2`` count every dispatch — synchronous verbs and
        served batches — by operand layout and Stage-2 implementation, and
        ``periodic`` counts the dispatches of periodic systems.
        """
        with self._cv:
            snap = self._engine.stats_snapshot()
            snap["unresolved"] = len(self._futures)
        snap["plan_cache"] = plan_cache_stats()
        snap["executable_cache"] = executable_cache_stats()
        snap["backend"] = {
            "name": self.backend.name,
            "interpret": self.backend.interpret_mode(),
        }
        snap["mesh"] = (
            None
            if self._mesh_devices is None
            else {
                "devices": len(self._mesh_devices),
                "platform": self._mesh_devices[0].platform,
                "signature": mesh_signature(self._mesh_devices),
            }
        )
        autotune: Dict[str, Any] = (
            self._refitter.stats_snapshot()
            if self._refitter is not None
            else {"mode": "off"}
        )
        autotune["observations"] = self._telemetry.counters()
        snap["autotune"] = autotune
        return snap

    def close(self) -> None:
        """Drain the queue (outstanding futures complete), stop the worker.

        Idempotent: further ``close()`` calls return immediately; ``submit``
        after close raises ``RuntimeError``. Synchronous verbs stay usable —
        only the serving side shuts down.
        """
        with self._cv:
            self._closed = True
            worker = self._worker
            self._cv.notify_all()
        if worker is not None:
            worker.join()

    def __enter__(self) -> "TridiagSession":
        return self

    def __exit__(self, exc_type: object, exc: object, tb: object) -> None:
        self.close()

    def __repr__(self) -> str:
        with self._cv:
            state = "closed" if self._closed else "open"
            pending = self._engine.pending()
        return (
            f"TridiagSession(m={self.config.m}, backend={self.backend.name!r}, "
            f"dispatch={self.config.dispatch!r}, {state}, "
            f"pending={pending})"
        )


# Convenience: the registry names a config's backend may take.
BACKEND_NAMES = tuple(sorted(BACKENDS))
