"""Declarative configuration of the repo's checked invariants.

Rules never hard-code repo names in their visitors; everything a rule flags
is driven by the entries here, so growing the codebase (a new cache, a new
lock, a new donating entry point) means *registering* the invariant, not
editing checker logic. Tests construct a custom :class:`Registry` to aim the
rules at fixture modules.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Tuple


@dataclass(frozen=True)
class GuardedGlobals:
    """Module-level shared state that must be touched under a lock (TRD001).

    ``module`` is a path suffix (``/``-separated) selecting the file the
    entry applies to; ``names`` are the module-global identifiers; ``guards``
    the lock names whose ``with`` block satisfies the rule. Module-level
    statements (the definitions themselves) are exempt; ``allow_in`` lists
    additional fully-qualified functions (``Class.method`` or bare function
    names) that may touch the state unguarded.
    """

    module: str
    names: Tuple[str, ...]
    guards: Tuple[str, ...]
    allow_in: Tuple[str, ...] = ()


@dataclass(frozen=True)
class GuardedAttrs:
    """Instance attributes that must be touched under a lock (TRD001).

    Matches any ``<expr>.<attr>`` access in ``module`` where ``attr`` is in
    ``attrs`` — attribute chains included (``self._engine._queue`` matches
    ``_queue``). ``guards`` are lock *attribute or global* names; ``owner``
    names the class the state belongs to (documentation + allowlist
    prefix). ``allow_in`` lists methods that are owner-serialised by
    contract (every caller holds the owner's lock around the whole call).
    """

    module: str
    owner: str
    attrs: Tuple[str, ...]
    guards: Tuple[str, ...]
    allow_in: Tuple[str, ...] = ()


@dataclass(frozen=True)
class DonatingCall:
    """A call site whose operands are donated to XLA (TRD002).

    ``constructors`` name the executor classes whose instances donate;
    ``method`` is the donating method; ``donated_args`` are the 0-based
    positions of the donated operands in the *method* call (keywords in
    ``donated_kwargs``); ``disable_kwarg`` names the constructor keyword
    that, when passed a ``False`` literal, turns donation off.
    """

    constructors: Tuple[str, ...] = ("FusedExecutor",)
    method: str = "execute"
    donated_args: Tuple[int, ...] = (1, 2, 3, 4)
    donated_kwargs: Tuple[str, ...] = ("dl", "d", "du", "b")
    disable_kwarg: str = "donate"


@dataclass(frozen=True)
class PurityConfig:
    """What counts as tracing, and what counts as impure (TRD003).

    ``tracers`` are dotted names that trace their function argument (or the
    function they decorate); ``impure_calls`` are flagged unconditionally
    inside a traced body; ``impure_prefixes`` likewise (dotted-prefix match,
    e.g. ``time.`` flags ``time.sleep``); ``host_array_prefixes`` are flagged
    only when the call's arguments involve a traced value (``np.asarray`` on
    a static tuple is legitimate trace-time constant folding, ``np.asarray``
    on a traced operand silently forces a host transfer or fails under jit);
    ``device_producers`` feed TRD002's device-array taint.
    """

    tracers: Tuple[str, ...] = (
        "jax.jit",
        "jit",
        "pl.pallas_call",
        "pallas_call",
        "jax.pmap",
    )
    impure_calls: Tuple[str, ...] = ("print", "input", "breakpoint", "open")
    impure_prefixes: Tuple[str, ...] = (
        "time.",
        "random.",
        "np.random.",
        "numpy.random.",
    )
    host_array_prefixes: Tuple[str, ...] = ("np.", "numpy.")
    device_producers: Tuple[str, ...] = (
        "jnp.",
        "jax.numpy.",
        "jax.device_put",
        "jax.random.",
    )


@dataclass(frozen=True)
class Registry:
    """Everything the rules know about this repo, in one declarative object."""

    guarded_globals: Tuple[GuardedGlobals, ...] = ()
    guarded_attrs: Tuple[GuardedAttrs, ...] = ()
    donating_calls: Tuple[DonatingCall, ...] = (DonatingCall(),)
    purity: PurityConfig = field(default_factory=PurityConfig)
    #: The public surface TRD005 audits (module, config class in its __all__).
    api_module: str = "repro.api"
    api_config_class: str = "SolverConfig"


#: The engine's queue-side state is owner-serialised: ``TridiagSession``
#: holds ``_cv`` around every engine call, and a direct caller of the engine
#: is documented single-threaded — so the engine's own methods are the
#: allowlist, and the rule's job is catching *outside* touches (a session
#: or test reaching into ``engine._queue`` without the lock).
_ENGINE_METHODS = tuple(
    f"SolveEngine.{name}"
    for name in (
        "__init__",
        "submit",
        "pending",
        "cancel",
        "shed_expired",
        "take_due_group",
        "_admit",
        "_take_group",
        "poll",
        "flush",
        "_drain",
        "_dispatch",
        "_oldest_submit",
        "seconds_to_deadline",
        "seconds_to_next_event",
        "_deadline_expired",
        "stats_snapshot",
        "shed_unmeetable",
    )
)

_PLAN_PY = "repro/core/tridiag/plan.py"
_API_PY = "repro/core/tridiag/api.py"
_TELEMETRY_RING_PY = "repro/telemetry/ring.py"
_TELEMETRY_REFIT_PY = "repro/telemetry/refit.py"
_PARALLEL_SOLVER_PY = "repro/parallel/solver.py"

DEFAULT_REGISTRY = Registry(
    guarded_globals=(
        GuardedGlobals(
            module=_PLAN_PY,
            names=(
                "_PLAN_CACHE",
                "_PLAN_STATS",
                "_PLAN_CACHE_CAPACITY",
                "_EXEC_CACHE",
                "_EXEC_STATS",
                "_EXEC_CACHE_CAPACITY",
                "_STAGE1_CACHE",
                "_STAGE3_CACHE",
                "_STAGE3_GHOST_CACHE",
                "_WIDE_STAGE1_CACHE",
                "_WIDE_STAGE3_CACHE",
            ),
            guards=("_CACHE_LOCK",),
        ),
        # The mesh memo is populated from caller and serving-worker threads
        # alike whenever a sharded executable is (re)built.
        GuardedGlobals(
            module=_PARALLEL_SOLVER_PY,
            names=("_MESH_CACHE",),
            guards=("_MESH_LOCK",),
        ),
    ),
    guarded_attrs=(
        GuardedAttrs(
            module=_API_PY,
            owner="SolveEngine",
            attrs=("stats", "_latency_model"),
            guards=("_stats_lock",),
            allow_in=("SolveEngine.__init__",),
        ),
        GuardedAttrs(
            module=_API_PY,
            owner="SolveEngine",
            attrs=("_queue", "_results", "_seq"),
            guards=("_cv",),
            allow_in=_ENGINE_METHODS,
        ),
        GuardedAttrs(
            module=_API_PY,
            owner="TridiagSession",
            attrs=(
                "_futures",
                "_worker",
                "_closed",
                "_worker_error",
                "_active_policy",
            ),
            guards=("_cv",),
            allow_in=("TridiagSession.__init__",),
        ),
        # The telemetry ring is written from the serving hot path and read by
        # the refitter/exporters on other threads: every touch of its window
        # and counters must hold its lock.
        GuardedAttrs(
            module=_TELEMETRY_RING_PY,
            owner="TelemetryBuffer",
            attrs=("_ring", "_recorded", "_dropped"),
            guards=("_lock",),
            allow_in=("TelemetryBuffer.__init__",),
        ),
        # The refitter's counters and last-fit results are read by
        # stats_snapshot()/last_heuristic() from any thread while the serve
        # worker refits; the fits themselves run outside the lock.
        GuardedAttrs(
            module=_TELEMETRY_REFIT_PY,
            owner="OnlineRefitter",
            attrs=(
                "_last_attempt_t",
                "_last_refit_t",
                "_attempts",
                "_refits",
                "_errors",
                "_agree",
                "_disagree",
                "_last_samples",
                "_last_heuristic",
                "_last_latency_model",
            ),
            guards=("_lock",),
            allow_in=("OnlineRefitter.__init__",),
        ),
    ),
)
