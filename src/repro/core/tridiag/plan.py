"""Plan/execute layer: one execution path for every partition-method solve.

The paper's end product is an *algorithm* that picks ``num_str`` before any
kernel launches; this module is the repo's structural analogue of that
"decide, then dispatch" split.  A :class:`SolvePlan` is an immutable layout
decision — which systems are fused onto the block axis, where the chunk
("virtual stream") boundaries fall, which halo block each chunk carries, and
where each system's solution lives in the fused vector.  A
:class:`PlanExecutor` then runs the three partition stages from the plan:

  Stage 1  per-chunk staged dispatch (H2D + kernel overlap — the CUDA-stream
           analogue, see ``chunked.py``'s module docstring for the mapping),
  Stage 2  host-side reduced solve (the paper keeps it on the CPU),
  Stage 3  per-chunk back-substitution with a ghost block for the left edge.

The front door (`api.TridiagSession` and its `SolveEngine`, plus the
deprecated solver-class wrappers that delegate to it) only *builds plans*;
chunk bounds, halo handling and ghost splicing live here and nowhere else.

The chunk count is either given explicitly or chosen by a pluggable
:class:`ChunkPolicy` — :class:`FixedChunkPolicy` or
:class:`HeuristicChunkPolicy`, which prices a (possibly ragged) batch by its
*effective size* ``Σ nᵢ`` through a fitted stream heuristic
(:func:`price_chunks` is the one pricing rule, shared with the serving path).

Stage backends
--------------
*How* the device stages run is a second pluggable axis, orthogonal to the
layout: a :class:`StageBackend` builds the stage-1/stage-3 callables the
executor dispatches per chunk. :class:`ReferenceBackend` (the default) jits
the pure-jnp ``partition.partition_stage{1,3}``; :class:`PallasBackend`
routes through the Pallas TPU kernels
(``repro.kernels.partition_stage{1,3}``), using their batched-grid variants
when the fused operands carry a leading batch axis. On this CPU container the
Pallas kernels run in interpret mode (``repro.kernels.common
.interpret_default``), so every planned path — single, batched, ragged,
serving — exercises the real kernel bodies under tier-1. Solvers and services
accept ``backend=`` (an instance or the registry names ``"reference"`` /
``"pallas"`` / ``"auto"``, where ``"auto"`` resolves to the Pallas kernels on
TPU hosts and the reference stages elsewhere); the jitted stages are cached
module-wide per ``(m, backend)``.

Plan cache
----------
``build_plan`` memoises plans by their ``(sizes, m, num_chunks, shards)``
signature (bounded LRU): serving traffic repeats batch compositions, and a
plan is a pure function of its signature, so repeated dispatches skip
replanning.
``plan_cache_stats()`` / ``clear_plan_cache()`` expose hit/miss counters for
tests and capacity planning; ``set_plan_cache_capacity()`` resizes the LRU
(``SolverConfig.plan_cache_capacity`` threads it through the facade).

Dispatch modes
--------------
*When* the stages are dispatched is the third axis. The classic
:class:`PlanExecutor` runs the **staged** path: per-chunk device dispatch
from a Python loop, a host round-trip for the Stage-2 reduced solve (the
paper keeps it on the CPU), then per-chunk back-substitution — the layout
that makes the per-phase :class:`ChunkTiming` breakdown (the paper's Eq. 5
decomposition) observable, and the path every ``measure_*`` campaign times.

:class:`FusedExecutor` is the **fused** path: for a given
``(plan, backend, operand dtypes, leading-batch shape)`` it traces the
*entire* three-stage solve — chunk slicing via ``lax.slice`` inside the
trace (halo blocks included), the reduced solve **on device**
(:class:`StageBackend.make_reduced_solve`: the jnp Thomas scan by default,
the ``repro.kernels.thomas`` Pallas kernel on the Pallas backend, which
partitions reduced systems too large for it recursively first), and the
ghost-block splicing of stage 3 — into ONE jitted callable with
``donate_argnums`` on the four diagonals. Zero host round-trips between
operand hand-off and solution split, and a single XLA dispatch instead of
the staged path's ~10 ops per chunk. Executables live in a bounded,
lock-protected LRU beside the plan cache
(:func:`executable_cache_stats` / :func:`clear_executable_cache` /
:func:`set_executable_cache_capacity`). Because the four diagonals are
donated, callers passing *device* arrays give up ownership (numpy operands
are copied to device per call and are always safe to reuse).

``SolverConfig.dispatch`` selects the mode per session: ``"staged"``,
``"fused"``, or ``"auto"`` (the default) — fused for the plain solve verbs
and the serving path, staged for the ``*_timed`` verbs so measurement
campaigns keep their phase breakdown.

Sharded dispatch
----------------
``SolverConfig.mesh`` (threaded through to ``FusedExecutor(mesh=...)``)
shards the fused executable across a 1-D device mesh: shard-aligned plans
(``build_plan(..., shards=S)``) split the block axis into equal per-device
spans, stage 1 and stage 3 run per-shard under ``shard_map`` with one
``ppermute`` halo exchange, and only the reduced system is gathered
(``all_gather`` of the per-shard reduced rows + a replicated device Stage-2
solve). Interleaved executables shard the lane axis instead, with no
collectives at all. See :func:`_sharded_fused_callable` and
:mod:`repro.parallel.solver`; the staged :class:`PlanExecutor` never shards
(its raison d'être is per-phase timing on one device).

Both module-level caches (plans and jitted stages) are lock-protected:
``TridiagSession.submit`` solves from a worker thread while the session's
synchronous verbs run on the caller's thread, so two threads legitimately
plan and fetch stages concurrently.
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec

from repro.core.tridiag import layout as layout_mod
from repro.core.tridiag import partition, spans
from repro.core.tridiag.layout import resolve_layout
from repro.core.tridiag.reference import thomas_numpy
from repro.core.tridiag.thomas import thomas as thomas_scan
from repro.parallel.solver import (
    MESH_AXIS_BATCH,
    MESH_AXIS_CHUNKS,
    mesh_for,
    mesh_signature,
    resolve_mesh_devices,
    shard_count,
)

Sizes = Union[int, Sequence[int]]


@dataclass
class ChunkTiming:
    """Wall-clock phase breakdown of one planned solve (milliseconds), and
    what ran: the operand ``layout``, the ``stage2`` implementation
    (``"host"`` for the staged path's ``thomas_numpy``, otherwise the name
    :meth:`StageBackend.reduced_solve_impl` gives) and whether the systems
    were ``periodic``."""

    num_chunks: int
    t_stage1_ms: float
    t_stage2_ms: float
    t_stage3_ms: float
    t_total_ms: float
    n: int = 0
    layout: str = "system-major"
    stage2: str = "host"
    periodic: bool = False

    @property
    def phases(self) -> Tuple[float, float, float]:
        return (self.t_stage1_ms, self.t_stage2_ms, self.t_stage3_ms)


def effective_size(sizes: Sizes) -> int:
    """Effective element count ``Σ nᵢ`` of a (possibly ragged) fused batch.

    A fused batch presents the device with one ``Σ nᵢ``-element solve, so this
    is the size feature the stream heuristic prices it by — the ragged
    generalisation of the ``n·B`` feature of the same-size batched campaign.
    """
    if isinstance(sizes, (int, np.integer)):
        return int(sizes)
    return int(sum(int(n) for n in sizes))


# ------------------------------------------------------------ stage backends --
class StageBackend:
    """How the executor's device stages are implemented.

    A backend builds the two callables `PlanExecutor` dispatches per chunk:
    ``make_stage1(m)`` returns ``(dl, d, du, b) -> PartitionCoeffs`` and
    ``make_stage3()`` returns ``(coeffs, s) -> x`` (back-substitution needs no
    block size) — both shape-polymorphic over an optional leading batch axis,
    both safe to call per chunk (jitted or wrapping jitted kernels). Backends
    must be hashable (frozen dataclasses): they key the module-level stage
    cache together with ``m``.

    ``make_reduced_solve(m)`` returns the *device-side* Stage-2 solver used
    by the fused dispatch path (``(red_dl, red_d, red_du, red_b) -> s``,
    traced into the fused executable of a plan with block size ``m``). The
    default is the pure-jnp Thomas scan; the Pallas backend routes 1-D/2-D
    reduced systems that fit VMEM through the ``repro.kernels.thomas`` kernel
    and partitions larger ones recursively on its Stage-1/Stage-3 kernels
    until they fit. :meth:`reduced_solve_impl` names the choice for a shape
    before anything is traced, so executors can report it, and
    :meth:`reduced_solve_levels` counts the recursion's levels. The staged
    path never calls it — its Stage 2 stays on the host (``thomas_numpy``),
    as in the paper.

    Periodic (cyclic) systems take the same factories with ``periodic=True``:
    stages whose neighbour shifts wrap round the block axis. Their reduced
    system is cyclic, and the fused path solves it with the plain reduced
    solve through :func:`repro.core.tridiag.partition.cyclic_solve`, whose
    rank-one correction is :meth:`periodic_update`.

    :meth:`check_dtype` refuses operand dtypes the backend cannot run, and
    :meth:`interpret_mode` says whether its kernels run interpreted (None
    for a backend without kernels).

    Operand *layout* is also a backend concern: the ``make_wide_*`` trio are
    the batch-interleaved (lane-major) counterparts, consuming wide operands
    as laid out by :mod:`repro.core.tridiag.layout` — stage 1 takes
    ``(P, m, B)`` diagonals and returns wide coeffs (spikes ``(P, m-1, B)``,
    reduced rows ``(P, B)``); the wide reduced solve runs B parallel length-P
    scans on ``(P, B)`` rows; wide stage 3 returns the ``(P, m, B)``
    solution. The base class supplies pure-jnp defaults, so every backend
    (including downstream subclasses) supports ``layout="interleaved"`` out
    of the box; `PallasBackend` overrides them with the wide-grid kernels.
    """

    name = "abstract"

    def make_stage1(self, m: int, periodic: bool = False) -> Callable:
        raise NotImplementedError

    def make_stage3(self, periodic: bool = False) -> Callable:
        raise NotImplementedError

    def periodic_update(self, y: Any, z: Any, beta: Any, axis: int) -> Any:
        """``y - beta * z``, the Sherman–Morrison correction of a cyclic
        reduced solve along ``axis`` of 2-D ``y`` (``beta`` of length 1
        there)."""
        return partition.rank_one_update(y, z, beta, axis)

    def make_reduced_solve(self, m: int) -> Callable:
        return thomas_scan

    def reduced_solve_impl(self, shape: Tuple[int, ...], dtype: Any) -> str:
        """The Stage-2 implementation ``make_reduced_solve`` runs on reduced
        rows of ``shape`` (``(..., P)``)."""
        return "thomas_scan"

    def reduced_solve_levels(self, shape: Tuple[int, ...], dtype: Any, m: int) -> int:
        """Partition levels that implementation takes before its direct
        solve (0 unless it is ``"partition_recursive"``)."""
        return 0

    def wide_reduced_solve_impl(self, shape: Tuple[int, ...], dtype: Any) -> str:
        """Same for ``make_wide_reduced_solve`` on ``(P, B)`` rows."""
        return "thomas_scan_wide"

    def interpret_mode(self) -> Optional[bool]:
        return None

    def check_dtype(self, dtype: Any) -> None:
        """Raise ``ValueError`` for operands this backend cannot run."""

    def make_wide_stage1(self, m: int, periodic: bool = False) -> Callable:
        return jax.jit(
            partial(layout_mod.partition_stage1_wide, m=m, periodic=periodic)
        )

    def make_wide_stage3(self, periodic: bool = False) -> Callable:
        return jax.jit(partial(layout_mod.partition_stage3_wide, periodic=periodic))

    def make_wide_reduced_solve(self) -> Callable:
        return layout_mod.thomas_wide


@dataclass(frozen=True)
class ReferenceBackend(StageBackend):
    """Jitted pure-jnp stages (``partition.partition_stage{1,3}``)."""

    name = "reference"

    def make_stage1(self, m: int, periodic: bool = False) -> Callable:
        return jax.jit(partial(partition.partition_stage1, m=m, periodic=periodic))

    def make_stage3(self, periodic: bool = False) -> Callable:
        return jax.jit(partial(partition.partition_stage3, periodic=periodic))


@dataclass(frozen=True)
class PallasBackend(StageBackend):
    """Pallas TPU kernel stages (`repro.kernels.partition_stage{1,3}`).

    Chunk operands with a leading batch axis route to the batched-grid kernel
    variants; 1-D fused operands (the single/batched/ragged fusion paths) use
    the single-system grid. ``interpret=None`` defers to
    ``repro.kernels.common.interpret_default()`` — interpret mode off-TPU, so
    the same backend object serves CPU tests and TPU runs.

    Compiled kernels take fp32 only (Mosaic has no fp64): fp64 operands are
    refused with a ``ValueError`` rather than downcast. The reduced solve
    stays on the Thomas kernel while its tiles fit VMEM
    (``repro.kernels.thomas.ops.thomas_fits_vmem``, decided from the shape);
    beyond that it is ``"partition_recursive"``: the Stage-1 and Stage-3
    kernels partition it at the plan's ``m``, as often as it takes to fit
    (:func:`repro.core.tridiag.partition.partition_solve_recursive`).
    Reduced rows with more than two dimensions take the XLA scan.
    """

    name = "pallas"
    block_p: int = 512
    # Wide (interleaved-layout) grid tiles: systems per lane-block and
    # partition blocks per grid step (see ``stage1_tiled_wide``).
    block_b: int = 256
    block_rows: int = 32
    interpret: Optional[bool] = None

    def make_stage1(self, m: int, periodic: bool = False) -> Callable:
        # Imported lazily: the kernel ops import repro.core.tridiag.partition,
        # whose package __init__ imports this module.
        from repro.kernels.partition_stage1.ops import (
            partition_stage1_pallas,
            partition_stage1_pallas_batched,
        )

        def stage1(dl: Any, d: Any, du: Any, b: Any) -> Any:
            ndim = jnp.asarray(d).ndim
            kw = dict(
                m=m, block_p=self.block_p, interpret=self.interpret, periodic=periodic
            )
            if ndim == 1:
                return partition_stage1_pallas(dl, d, du, b, **kw)
            if ndim == 2:
                return partition_stage1_pallas_batched(dl, d, du, b, **kw)
            raise ValueError(
                f"PallasBackend stage 1 takes (n,) or (batch, n) operands, "
                f"got {ndim}-D"
            )

        return stage1

    def make_stage3(self, periodic: bool = False) -> Callable:
        from repro.kernels.partition_stage3.ops import (
            partition_stage3_pallas,
            partition_stage3_pallas_batched,
        )

        def stage3(coeffs: Any, s: Any) -> Any:
            # The host reduced solve is fp64 (oracle of record); the jnp
            # reference stage promotes silently, but kernel refs are typed —
            # back-substitution runs in the spikes' precision.
            s = jnp.asarray(s, dtype=jnp.asarray(coeffs.y).dtype)
            ndim = s.ndim
            kw = dict(block_p=self.block_p, interpret=self.interpret, periodic=periodic)
            if ndim == 1:
                return partition_stage3_pallas(coeffs, s, **kw)
            if ndim == 2:
                return partition_stage3_pallas_batched(coeffs, s, **kw)
            raise ValueError(
                f"PallasBackend stage 3 takes (P,) or (batch, P) interface "
                f"operands, got {ndim}-D"
            )

        return stage3

    def interpret_mode(self) -> Optional[bool]:
        from repro.kernels.common import interpret_default

        return interpret_default() if self.interpret is None else self.interpret

    def check_dtype(self, dtype: Any) -> None:
        if self.interpret_mode():
            return  # interpreted kernels are plain XLA ops: fp64 runs
        if np.dtype(jax.dtypes.canonicalize_dtype(dtype)) == np.float64:
            raise ValueError(
                "fp64 operands cannot run on the compiled Pallas kernels "
                "(Mosaic has no fp64); pass SolverConfig(dtype=np.float32) "
                "or fp32 operands"
            )

    @staticmethod
    def _thomas_fits(shape: Tuple[int, ...], dtype: Any) -> Callable[[int], bool]:
        """Whether the Thomas kernel takes P rows with ``shape``'s lanes."""
        from repro.kernels.thomas.ops import thomas_fits_vmem

        lanes = shape[0] if len(shape) == 2 else 1
        itemsize = np.dtype(dtype).itemsize
        return lambda p: thomas_fits_vmem(p, lanes, itemsize)

    def reduced_solve_impl(self, shape: Tuple[int, ...], dtype: Any) -> str:
        # The kernel's grid is (batch,)-tiled: 1-D and 2-D reduced systems
        # route through it, partitioned first if its tiles do not fit VMEM;
        # exotic extra leading dims take the scan.
        if len(shape) > 2:
            return "thomas_scan"
        if self._thomas_fits(shape, dtype)(shape[-1]):
            return "thomas_pallas"
        return "partition_recursive"

    def reduced_solve_levels(self, shape: Tuple[int, ...], dtype: Any, m: int) -> int:
        if len(shape) > 2:
            return 0
        return partition.partition_levels(shape[-1], m, self._thomas_fits(shape, dtype))

    def make_reduced_solve(self, m: int) -> Callable:
        from repro.kernels.thomas.ops import thomas_pallas

        stage1, stage3 = jitted_stages(m, self)
        direct = partial(thomas_pallas, interpret=self.interpret)

        def reduced_solve(red_dl: Any, red_d: Any, red_du: Any, red_b: Any) -> Any:
            red_d = jnp.asarray(red_d)
            if self.reduced_solve_impl(red_d.shape, red_d.dtype) == "thomas_scan":
                return thomas_scan(red_dl, red_d, red_du, red_b)
            # On a system that fits this is the direct kernel call alone.
            return partition.partition_solve_recursive(
                red_dl, red_d, red_du, red_b,
                m=m, stage1=stage1, stage3=stage3, direct=direct,
                fits=self._thomas_fits(red_d.shape, red_d.dtype),
            )

        return reduced_solve

    def make_wide_stage1(self, m: int, periodic: bool = False) -> Callable:
        from repro.kernels.partition_stage1.ops import partition_stage1_pallas_wide

        return partial(
            partition_stage1_pallas_wide,
            m=m,
            block_rows=self.block_rows,
            block_b=self.block_b,
            interpret=self.interpret,
            periodic=periodic,
        )

    def make_wide_stage3(self, periodic: bool = False) -> Callable:
        from repro.kernels.partition_stage3.ops import partition_stage3_pallas_wide

        def wide_stage3(coeffs: Any, s: Any) -> Any:
            # Same precision contract as make_stage3: kernel refs are typed,
            # so a host-fp64 interface vector is cast to the spikes' dtype.
            s = jnp.asarray(s, dtype=jnp.asarray(coeffs.y).dtype)
            return partition_stage3_pallas_wide(
                coeffs,
                s,
                block_rows=self.block_rows,
                block_b=self.block_b,
                interpret=self.interpret,
                periodic=periodic,
            )

        return wide_stage3

    def periodic_update(self, y: Any, z: Any, beta: Any, axis: int) -> Any:
        from repro.kernels.periodic.ops import periodic_correction_pallas

        return periodic_correction_pallas(
            y, z, beta, axis=axis, interpret=self.interpret
        )

    def wide_reduced_solve_impl(self, shape: Tuple[int, ...], dtype: Any) -> str:
        from repro.kernels.thomas.ops import thomas_fits_vmem

        p, lanes = shape
        if thomas_fits_vmem(p, lanes, np.dtype(dtype).itemsize, self.block_b):
            return "thomas_pallas_wide"
        return "thomas_scan_wide"

    def make_wide_reduced_solve(self) -> Callable:
        from repro.kernels.thomas.ops import thomas_pallas_wide

        def wide_reduced_solve(
            red_dl: Any, red_d: Any, red_du: Any, red_b: Any
        ) -> Any:
            red_d = jnp.asarray(red_d)
            impl = self.wide_reduced_solve_impl(red_d.shape, red_d.dtype)
            if impl == "thomas_pallas_wide":
                return thomas_pallas_wide(
                    red_dl, red_d, red_du, red_b,
                    block_b=self.block_b, interpret=self.interpret,
                )
            return layout_mod.thomas_wide(red_dl, red_d, red_du, red_b)

        return wide_reduced_solve


@dataclass(frozen=True)
class AutoBackend(StageBackend):
    """Hardware-resolved backend: Pallas kernels on TPU hosts, reference
    elsewhere (the ROADMAP PR-3 follow-up, and ``SolverConfig``'s default).

    :func:`resolve_backend` unwraps it eagerly, so the module-level stage
    cache only ever keys *concrete* backends — ``"auto"`` and the name it
    resolves to share one cache entry.
    """

    name = "auto"

    def resolve(self) -> StageBackend:
        return BACKENDS["pallas" if jax.default_backend() == "tpu" else "reference"]

    def make_stage1(self, m: int, periodic: bool = False) -> Callable:
        return self.resolve().make_stage1(m, periodic)

    def make_stage3(self, periodic: bool = False) -> Callable:
        return self.resolve().make_stage3(periodic)

    def periodic_update(self, y: Any, z: Any, beta: Any, axis: int) -> Any:
        return self.resolve().periodic_update(y, z, beta, axis)

    def make_reduced_solve(self, m: int) -> Callable:
        return self.resolve().make_reduced_solve(m)

    def reduced_solve_impl(self, shape: Tuple[int, ...], dtype: Any) -> str:
        return self.resolve().reduced_solve_impl(shape, dtype)

    def reduced_solve_levels(self, shape: Tuple[int, ...], dtype: Any, m: int) -> int:
        return self.resolve().reduced_solve_levels(shape, dtype, m)

    def wide_reduced_solve_impl(self, shape: Tuple[int, ...], dtype: Any) -> str:
        return self.resolve().wide_reduced_solve_impl(shape, dtype)

    def interpret_mode(self) -> Optional[bool]:
        return self.resolve().interpret_mode()

    def check_dtype(self, dtype: Any) -> None:
        self.resolve().check_dtype(dtype)

    def make_wide_stage1(self, m: int, periodic: bool = False) -> Callable:
        return self.resolve().make_wide_stage1(m, periodic)

    def make_wide_stage3(self, periodic: bool = False) -> Callable:
        return self.resolve().make_wide_stage3(periodic)

    def make_wide_reduced_solve(self) -> Callable:
        return self.resolve().make_wide_reduced_solve()


#: Registry consulted when ``backend=`` is given as a string; keys are the
#: backends' ``name`` attributes.
BACKENDS: Dict[str, StageBackend] = {
    b.name: b for b in (ReferenceBackend(), PallasBackend(), AutoBackend())
}

BackendLike = Union[StageBackend, str, None]


def resolve_backend(backend: BackendLike) -> StageBackend:
    """Normalise a ``backend=`` argument: None → reference, str → registry,
    ``"auto"``/:class:`AutoBackend` → whichever concrete backend fits this
    host (Pallas on TPU, reference elsewhere)."""
    if backend is None:
        return BACKENDS["reference"]
    if isinstance(backend, str):
        try:
            backend = BACKENDS[backend]
        except KeyError:
            raise ValueError(
                f"unknown stage backend {backend!r}; known: {sorted(BACKENDS)}"
            ) from None
    if isinstance(backend, AutoBackend):
        return backend.resolve()
    if isinstance(backend, StageBackend):
        return backend
    raise TypeError(f"backend must be a StageBackend, name or None, got {backend!r}")


# ------------------------------------------------------------ jitted stages --
# Module-level cache of the stage callables. Stage 1 is keyed by
# (m, backend, periodic); stage 3 takes no block size, so one callable per
# (backend, periodic) serves every m. Frontends and services construct
# solver objects freely (one per chunk count, per request batch, per sweep
# cell); tracing/compilation must not follow suit. The callables are
# batch-polymorphic (leading dims pass through), so each cached pair serves
# the single, batched and ragged paths alike; jax.jit specialises per
# operand shape internally.
#
# _CACHE_LOCK guards both stage caches and the plan cache below: a
# TridiagSession dispatches from its worker thread while its synchronous
# verbs (and other sessions) run on caller threads, and interleaved dict/LRU
# mutation would corrupt the OrderedDict order or drop entries.
_CACHE_LOCK = threading.RLock()
_STAGE1_CACHE: Dict[Tuple[int, StageBackend, bool], Callable] = {}
_STAGE3_CACHE: Dict[Tuple[StageBackend, bool], Callable] = {}
_STAGE3_GHOST_CACHE: Dict[StageBackend, Callable] = {}
_WIDE_STAGE1_CACHE: Dict[Tuple[int, StageBackend, bool], Callable] = {}
_WIDE_STAGE3_CACHE: Dict[Tuple[StageBackend, bool], Callable] = {}


def jitted_stages(
    m: int, backend: BackendLike = None, periodic: bool = False
) -> Tuple[Callable, Callable]:
    """Return the cached ``(stage1, stage3)`` callables for ``(m, backend)``,
    of cyclic systems with ``periodic``."""
    backend = resolve_backend(backend)
    key, key3 = (m, backend, periodic), (backend, periodic)
    # make_stage{1,3} only build (cheap) wrappers — tracing happens at first
    # call — so holding the lock across them is fine and keeps one winner.
    with _CACHE_LOCK:
        if key not in _STAGE1_CACHE:
            _STAGE1_CACHE[key] = backend.make_stage1(m, periodic)
        if key3 not in _STAGE3_CACHE:
            _STAGE3_CACHE[key3] = backend.make_stage3(periodic)
        return _STAGE1_CACHE[key], _STAGE3_CACHE[key3]


def jitted_wide_stages(
    m: int, backend: BackendLike = None, periodic: bool = False
) -> Tuple[Callable, Callable]:
    """Cached ``(wide_stage1, wide_stage3)`` — the interleaved-layout twins
    of :func:`jitted_stages`, consuming (P, m, B) operands (systems on the
    minor axis; see :mod:`repro.core.tridiag.layout`)."""
    backend = resolve_backend(backend)
    key, key3 = (m, backend, periodic), (backend, periodic)
    with _CACHE_LOCK:
        if key not in _WIDE_STAGE1_CACHE:
            _WIDE_STAGE1_CACHE[key] = backend.make_wide_stage1(m, periodic)
        if key3 not in _WIDE_STAGE3_CACHE:
            _WIDE_STAGE3_CACHE[key3] = backend.make_wide_stage3(periodic)
        return _WIDE_STAGE1_CACHE[key], _WIDE_STAGE3_CACHE[key3]


def jitted_stage3_ghost(backend: BackendLike = None) -> Callable:
    """Cached jitted ``(coeffs, s_chunk, s_left_edge) -> x`` per backend.

    One dispatch per chunk for the whole ghost-splice + back-substitution:
    the ghost-block construction of :func:`_stage3_with_ghost` (seven
    ``zeros_like`` + eight concatenates + the stage-3 call + a slice) used to
    issue ~10 tiny device ops from Python per chunk; jitting the helper fuses
    them into one executable per chunk shape.
    """
    backend = resolve_backend(backend)
    with _CACHE_LOCK:
        fn = _STAGE3_GHOST_CACHE.get(backend)
        if fn is None:
            key3 = (backend, False)
            if key3 not in _STAGE3_CACHE:
                _STAGE3_CACHE[key3] = backend.make_stage3()
            fn = jax.jit(partial(_stage3_with_ghost, _STAGE3_CACHE[key3]))
            _STAGE3_GHOST_CACHE[backend] = fn
        return fn


# ------------------------------------------------------------ chunk policies --
def price_chunks(heuristic: Any, sizes: Sizes, *, fp32: bool = False) -> int:
    """THE chunk-pricing rule: one heuristic call for every entry point.

    `HeuristicChunkPolicy` and `serve.solve.BatchedSolveService` both route
    through here, so a batch can never get a different chunk count depending
    on whether it arrives via a plan policy or the serving queue. Heuristics
    exposing ``predict_optimum_ragged`` (the batched/ragged-aware pricing) are
    preferred; plain 1-D heuristics are priced at the batch's effective size
    ``Σ nᵢ``. The paper's FP32 rule (§3.2: halve the FP64 optimum) applies on
    top of either path. The result is clamped to ``>= 1`` here — a fitted
    heuristic can round to 0 on tiny effective sizes, and the serving queue
    passes this pick to ``build_plan`` as an *explicit* count, which is
    strict by contract.
    """
    if isinstance(sizes, (int, np.integer)):
        sizes = (int(sizes),)
    sizes = tuple(int(n) for n in sizes)
    if fp32 and hasattr(heuristic, "predict_optimum_fp32"):
        # The heuristic's own FP32 rule wins (at the batch's effective size);
        # the halving below is only the fallback for ragged-aware heuristics
        # that never fitted one.
        k = int(heuristic.predict_optimum_fp32(float(effective_size(sizes))))
    elif hasattr(heuristic, "predict_optimum_ragged"):
        k = int(heuristic.predict_optimum_ragged(sizes))
        if fp32:
            k //= 2
    else:
        k = int(heuristic.predict_optimum(float(effective_size(sizes))))
        if fp32:
            k //= 2
    return max(1, k)


class ChunkPolicy:
    """Strategy choosing the chunk ("virtual stream") count for a plan.

    Subclasses implement :meth:`num_chunks`; `build_plan` clamps the answer
    to ``[1, num_blocks]``.
    """

    def num_chunks(self, sizes: Tuple[int, ...], m: int) -> int:
        raise NotImplementedError


@dataclass(frozen=True)
class FixedChunkPolicy(ChunkPolicy):
    """Always use ``k`` chunks (the paper's fixed-``num_str`` baseline)."""

    k: int

    def num_chunks(self, sizes: Tuple[int, ...], m: int) -> int:
        return self.k


@dataclass(frozen=True)
class HeuristicChunkPolicy(ChunkPolicy):
    """Price the batch by its effective size through a fitted heuristic.

    Accepts either a 1-D ``StreamHeuristic`` or a ``BatchedStreamHeuristic``;
    the pricing is delegated to :func:`price_chunks` (shared with the serving
    queue), which prefers ``predict_optimum_ragged`` and otherwise prices the
    batch at its effective size ``effective_size(sizes)`` — so ragged
    mixed-size batches are priced exactly like the same-size fused batch with
    the same total element count, whichever entry point they arrive through.
    """

    heuristic: object
    fp32: bool = False

    def num_chunks(self, sizes: Tuple[int, ...], m: int) -> int:
        return price_chunks(self.heuristic, sizes, fp32=self.fp32)


# ----------------------------------------------------------------- the plan --
@dataclass(frozen=True)
class SolvePlan:
    """Immutable layout of one fused chunked partition solve.

    ``sizes`` lists the fused systems in order (one entry per system; a single
    solve is the 1-tuple); ``chunk_bounds`` are half-open block-index ranges
    over the fused block axis; ``halo_bounds`` extend each chunk by its one
    right halo block (the reduced row of a chunk's last block references the
    next block's spikes); ``offsets`` is the per-system element offset table
    (length B+1) used to split the fused solution back apart.

    ``shards`` is the shard-aligned mode (``build_plan(..., shards=S)``): the
    block axis is split into ``S`` equal spans (``S`` divides ``num_blocks``
    and ``num_chunks``), every span boundary coincides with a chunk boundary,
    and every span carries the same chunk layout — so a device mesh can own
    one span per device, the halo map degenerates to one per-shard exchange
    (each shard needs only the *next* shard's first block), and the in-shard
    chunk loop is the same static program on every device
    (:attr:`local_chunk_bounds`). ``shards=1`` is today's unsharded plan.

    ``periodic`` plans lay out cyclic systems (``build_plan(...,
    periodic=True)``): same-size systems, each closing on itself, so each
    keeps its own wrapped block axis. They are never fused end to end (a
    corner would couple neighbouring systems), never chunked and never
    sharded along the block axis: one chunk, one shard. A periodic plan is
    never equal to a non-periodic one, so the two never share an
    executable.
    """

    m: int
    sizes: Tuple[int, ...]
    chunk_bounds: Tuple[Tuple[int, int], ...]
    halo_bounds: Tuple[Tuple[int, int], ...]
    offsets: Tuple[int, ...]
    shards: int = 1
    periodic: bool = False

    @property
    def batch(self) -> int:
        return len(self.sizes)

    @property
    def total_size(self) -> int:
        return self.offsets[-1]

    @property
    def num_blocks(self) -> int:
        return self.total_size // self.m

    @property
    def num_chunks(self) -> int:
        return len(self.chunk_bounds)

    @property
    def effective_size(self) -> int:
        return self.total_size

    @property
    def blocks_per_shard(self) -> int:
        return self.num_blocks // self.shards

    @property
    def local_chunk_bounds(self) -> Tuple[Tuple[int, int], ...]:
        """One shard's chunk bounds, relative to the shard's first block.

        Valid by construction (shard-aligned plans repeat the same chunk
        layout in every shard), so the sharded executor traces one static
        in-shard chunk loop that is correct on every device.
        """
        return self.chunk_bounds[: self.num_chunks // self.shards]


# ------------------------------------------------------------- plan cache --
# Plans are pure functions of their (sizes, m, num_chunks) signature, and
# serving traffic repeats batch compositions (same mix of request sizes →
# identical fused layout), so build_plan memoises them in a bounded LRU. The
# capacity bounds memory for adversarial traffic with no repeated mixes;
# 1024 distinct compositions is far beyond any steady-state queue.
_PLAN_CACHE_CAPACITY = 1024
_PLAN_CACHE: "OrderedDict[Tuple[Tuple[int, ...], int, int, int, bool], SolvePlan]" = (
    OrderedDict()
)
_PLAN_STATS = {"hits": 0, "misses": 0}


def plan_cache_stats() -> Dict[str, int]:
    """Hit/miss counters of the build_plan memo (plus its current size)."""
    with _CACHE_LOCK:
        return {**_PLAN_STATS, "size": len(_PLAN_CACHE)}


def clear_plan_cache() -> None:
    """Empty the plan memo and reset its counters (test isolation hook)."""
    with _CACHE_LOCK:
        _PLAN_CACHE.clear()
        _PLAN_STATS["hits"] = 0
        _PLAN_STATS["misses"] = 0


def set_plan_cache_capacity(capacity: int) -> None:
    """Resize the plan LRU (process-wide); 0 disables plan memoisation.

    Cached plans beyond the new capacity are evicted oldest-first.
    ``SolverConfig.plan_cache_capacity`` applies this at session construction
    for deployments that want a bigger memo (many distinct batch
    compositions) or none at all (adversarial traffic).
    """
    global _PLAN_CACHE_CAPACITY
    if capacity < 0:
        raise ValueError(f"plan cache capacity must be >= 0, got {capacity}")
    with _CACHE_LOCK:
        _PLAN_CACHE_CAPACITY = int(capacity)
        while len(_PLAN_CACHE) > _PLAN_CACHE_CAPACITY:
            _PLAN_CACHE.popitem(last=False)


def build_plan(
    sizes: Sizes,
    m: int = 10,
    *,
    num_chunks: Optional[int] = None,
    policy: Optional[ChunkPolicy] = None,
    shards: int = 1,
    periodic: bool = False,
) -> SolvePlan:
    """Build the :class:`SolvePlan` for a batch of systems of ``sizes``.

    ``sizes`` is one int (single solve) or a sequence (fused batch, possibly
    ragged). Exactly one of ``num_chunks``/``policy`` may be given; with
    neither, the plan is unchunked (``num_chunks=1``). The chunk count is
    clamped into ``[1, num_blocks]`` — in particular a :class:`ChunkPolicy`
    may legitimately round to 0 on tiny effective sizes (a fitted heuristic's
    Eq.-6 sweep near the origin) and is clamped up rather than rejected, so a
    policy pick can never kill a dispatch. An *explicit* ``num_chunks < 1``
    is still a caller error. Blocks are split as evenly as possible
    (remainder blocks go to the leading chunks).

    ``shards`` requests the shard-aligned mode for mesh execution: the count
    is snapped down to the largest divisor of ``num_blocks`` within the
    request (so an 8-device mesh over a prime block count degrades to the
    unsharded plan instead of erroring), the chunk count is snapped to a
    multiple of the shard count (every shard gets the same number of chunks,
    every shard boundary is a chunk boundary), and the plan records the
    result in :attr:`SolvePlan.shards`. ``shards=1`` (the default) is
    exactly today's layout.

    ``periodic`` plans cyclic systems (:attr:`SolvePlan.periodic`): the
    sizes must be equal, and the plan is unchunked and unsharded, so
    ``num_chunks``, ``policy`` and ``shards`` beyond one are refused.

    Plans are memoised by their ``(sizes, m, num_chunks, shards, periodic)``
    signature in a bounded module-level LRU (policies are consulted first,
    then the resolved counts key the cache), so serving traffic that repeats
    a batch composition skips replanning; see :func:`plan_cache_stats`.
    """
    if isinstance(sizes, (int, np.integer)):
        sizes = (int(sizes),)
    sizes = tuple(int(n) for n in sizes)
    if not sizes:
        raise ValueError("empty plan: at least one system required")
    if m < 2:
        raise ValueError("sub-system size m must be >= 2")
    for n in sizes:
        if n < m or n % m:
            raise ValueError(f"system size {n} not divisible by m={m}")
    if num_chunks is not None and policy is not None:
        raise ValueError("pass num_chunks or policy, not both")
    if periodic:
        if len(set(sizes)) != 1:
            raise ValueError(f"periodic plans take same-size systems, got {sizes}")
        if policy is not None or (num_chunks or 1) != 1 or shards != 1:
            raise ValueError(
                "periodic plans are unchunked and unsharded: a chunk's halo and "
                "a shard's span do not wrap round a cyclic system"
            )
    if policy is not None:
        # Clamp the policy's pick into [1, num_blocks] exactly like the upper
        # bound below: heuristics may round to 0 on tiny effective sizes.
        k = max(1, int(policy.num_chunks(sizes, m)))
    else:
        k = 1 if num_chunks is None else int(num_chunks)
        if k < 1:
            raise ValueError("num_chunks must be >= 1")

    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")

    num_blocks = sum(sizes) // m
    k = min(k, num_blocks)
    # Shard-aligned mode: snap the shard count to a divisor of the block
    # axis (shard_map needs equal spans), then snap the chunk count to a
    # multiple of it so every span boundary is a chunk boundary and every
    # span repeats the same in-shard chunk layout.
    shards = shard_count(num_blocks, int(shards))
    if shards > 1:
        per_shard_blocks = num_blocks // shards
        per_shard_chunks = max(1, min(per_shard_blocks, round(k / shards)))
        k = per_shard_chunks * shards

    key = (sizes, m, k, shards, periodic)
    with _CACHE_LOCK:
        cached = _PLAN_CACHE.get(key)
        if cached is not None:
            _PLAN_CACHE.move_to_end(key)
            _PLAN_STATS["hits"] += 1
            return cached
        _PLAN_STATS["misses"] += 1

    bounds: List[Tuple[int, int]] = []
    if shards > 1:
        # k/shards chunks over num_blocks/shards blocks, repeated per shard:
        # identical local layout on every shard by construction.
        cps = k // shards
        local_sizes = [
            per_shard_blocks // cps + (1 if i < per_shard_blocks % cps else 0)
            for i in range(cps)
        ]
        start = 0
        for _ in range(shards):
            for s in local_sizes:
                bounds.append((start, start + s))
                start += s
    else:
        chunk_sizes = [
            num_blocks // k + (1 if i < num_blocks % k else 0) for i in range(k)
        ]
        start = 0
        for s in chunk_sizes:
            bounds.append((start, start + s))
            start += s
    halos = tuple((lo, min(hi + 1, num_blocks)) for lo, hi in bounds)

    offsets = [0]
    for n in sizes:
        offsets.append(offsets[-1] + n)
    plan = SolvePlan(
        m=m,
        sizes=sizes,
        chunk_bounds=tuple(bounds),
        halo_bounds=halos,
        offsets=tuple(offsets),
        shards=shards,
        periodic=periodic,
    )
    with _CACHE_LOCK:
        # A racing thread may have built the same plan between the lookup and
        # here; keep its entry so hits keep returning one shared object.
        existing = _PLAN_CACHE.get(key)
        if existing is not None:
            return existing
        _PLAN_CACHE[key] = plan
        while len(_PLAN_CACHE) > _PLAN_CACHE_CAPACITY:
            _PLAN_CACHE.popitem(last=False)
    return plan


# ------------------------------------------------------- executable cache --
# The fused dispatch path compiles one end-to-end executable per
# (plan, backend, donate, operand dtypes, leading-batch shape) signature.
# Executables are much heavier than plans (a full XLA compilation each), so
# they get their own bounded LRU beside the plan cache, guarded by the same
# _CACHE_LOCK (sessions hit it from worker + caller threads concurrently).
_EXEC_CACHE_CAPACITY = 128
_EXEC_CACHE: "OrderedDict[Tuple, Callable]" = OrderedDict()
_EXEC_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def executable_cache_stats() -> Dict[str, int]:
    """Hit/miss/eviction counters of the fused-executable LRU (plus size)."""
    with _CACHE_LOCK:
        return {**_EXEC_STATS, "size": len(_EXEC_CACHE)}


def clear_executable_cache() -> None:
    """Empty the fused-executable LRU and reset its counters (test hook)."""
    with _CACHE_LOCK:
        _EXEC_CACHE.clear()
        _EXEC_STATS["hits"] = 0
        _EXEC_STATS["misses"] = 0
        _EXEC_STATS["evictions"] = 0


def set_executable_cache_capacity(capacity: int) -> None:
    """Resize the fused-executable LRU (process-wide); 0 disables caching
    (every fused dispatch then rebuilds + recompiles — only useful to bound
    memory under adversarial never-repeating traffic)."""
    global _EXEC_CACHE_CAPACITY
    if capacity < 0:
        raise ValueError(f"executable cache capacity must be >= 0, got {capacity}")
    with _CACHE_LOCK:
        _EXEC_CACHE_CAPACITY = int(capacity)
        while len(_EXEC_CACHE) > _EXEC_CACHE_CAPACITY:
            _EXEC_CACHE.popitem(last=False)
            _EXEC_STATS["evictions"] += 1


# -------------------------------------------------------------- the executor --
class PlanExecutor:
    """Runs stage-1 dispatch, host reduced solve and stage-3 from a plan.

    ``backend`` (a :class:`StageBackend`, a registry name, or None for the
    reference stages) decides *how* the chunked device stages execute; the
    executor itself carries no mutable state — the stage callables come from
    the module-level ``(m, backend)`` cache, so executors (and the frontends
    that own them) are free to construct. Operands are the *fused*
    diagonals/RHS — 1-D over ``plan.total_size``, or with extra leading dims
    that pass straight through the stages (on `PallasBackend` a single
    leading batch axis routes to the batched-grid kernels).

    ``layout`` picks the operand layout for the device stages. The default
    ``"auto"`` resolves to system-major on this (staged) executor — the
    chunked per-phase timing campaigns are its raison d'être, and chunk
    bounds slice the system-major block axis. An explicit ``"interleaved"``
    runs the whole-batch wide-stage variant instead (one lane-major stage-1
    and stage-3 dispatch, host reduced solve on (P, B) rows): per-phase
    timing stays observable, but the plan's chunk partition does not apply —
    the wide grid itself is the parallel axis.
    """

    def __init__(
        self, backend: BackendLike = None, *, layout: str = "auto"
    ) -> None:
        self.backend = resolve_backend(backend)
        if layout not in layout_mod.LAYOUTS:
            raise ValueError(
                f"layout must be one of {layout_mod.LAYOUTS}, got {layout!r}"
            )
        self.layout = layout

    def execute(
        self,
        plan: SolvePlan,
        dl: np.ndarray,
        d: np.ndarray,
        du: np.ndarray,
        b: np.ndarray,
    ) -> Tuple[np.ndarray, ChunkTiming]:
        m = plan.m
        n = int(np.shape(d)[-1])
        if n != plan.total_size:
            raise ValueError(
                f"operands have {n} rows but the plan lays out {plan.total_size}"
            )
        if plan.periodic:
            raise ValueError(
                "periodic plans run on the fused executor only: the staged "
                "path's chunks and host Stage 2 do not wrap round the systems"
            )
        self.backend.check_dtype(d.dtype if hasattr(d, "dtype") else np.result_type(d))
        layout = resolve_layout(
            self.layout, plan.sizes, m, fused=False, lead_ndim=np.ndim(d) - 1
        )
        if layout == "interleaved":
            return self._execute_interleaved(plan, dl, d, du, b)

        def row(a: Any, lo: int, hi: int) -> jax.Array:
            # Fast path: operands already on device slice lazily — no host
            # copy, no device_put (the PR-3 ROADMAP follow-up's staged half).
            if isinstance(a, jax.Array):
                return a[..., lo * m : hi * m]
            return jax.device_put(
                np.ascontiguousarray(np.asarray(a)[..., lo * m : hi * m])
            )  # H2D analogue

        stage1, _ = jitted_stages(m, self.backend)
        stage3_ghost = jitted_stage3_ghost(self.backend)

        t0 = time.perf_counter()
        # ---- Stage 1: dispatch every chunk without blocking (the "streams").
        # Each chunk carries one halo block (plan.halo_bounds): the reduced row
        # of a chunk's last block references the *next* block's spikes, so
        # chunks overlap by one block and the halo's own reduced row is dropped
        # (recomputed by the owner chunk) — the standard halo-exchange trick.
        coeffs: List[partition.PartitionCoeffs] = []
        for (lo, hi), (_, hi_halo) in zip(plan.chunk_bounds, plan.halo_bounds):
            chunk = [row(a, lo, hi_halo) for a in (dl, d, du, b)]
            c = stage1(*chunk)
            nb = hi - lo
            c = partition.PartitionCoeffs(
                y=c.y[..., :nb, :],
                v=c.v[..., :nb, :],
                w=c.w[..., :nb, :],
                red_dl=c.red_dl[..., :nb],
                red_d=c.red_d[..., :nb],
                red_du=c.red_du[..., :nb],
                red_b=c.red_b[..., :nb],
            )
            coeffs.append(c)
        # Block only when the host needs the reduced rows (D2H analogue).
        red = [
            np.concatenate([np.asarray(getattr(c, f)) for c in coeffs], axis=-1)
            for f in ("red_dl", "red_d", "red_du", "red_b")
        ]
        t1 = time.perf_counter()

        # ---- Stage 2: host-side reduced solve (paper: CPU).
        s = thomas_numpy(*red)
        t2 = time.perf_counter()

        # ---- Stage 3: per-chunk back-substitution; chunk p needs s_{p-1}, s_p.
        # One jitted dispatch per chunk: the ghost splice is fused into the
        # cached stage3_ghost callable instead of ~10 tiny ops from Python.
        outs = []
        for (lo, hi), c in zip(plan.chunk_bounds, coeffs):
            s_chunk = s[..., lo:hi]
            s_left_edge = (
                np.zeros_like(s_chunk[..., :1])
                if lo == 0
                else s[..., lo - 1 : lo]
            )
            outs.append(stage3_ghost(c, s_chunk, s_left_edge))
        x = np.concatenate([np.asarray(o) for o in outs], axis=-1)
        t3 = time.perf_counter()

        timing = ChunkTiming(
            num_chunks=plan.num_chunks,
            t_stage1_ms=(t1 - t0) * 1e3,
            t_stage2_ms=(t2 - t1) * 1e3,
            t_stage3_ms=(t3 - t2) * 1e3,
            t_total_ms=(t3 - t0) * 1e3,
            n=n,
        )
        return x, timing

    def _execute_interleaved(
        self, plan: SolvePlan, dl: Any, d: Any, du: Any, b: Any
    ) -> Tuple[np.ndarray, ChunkTiming]:
        """Whole-batch staged solve on the wide (lane-major) layout.

        Same three-phase structure as :meth:`execute` — device stage 1, host
        fp64 reduced solve, device stage 3 — but on interleaved operands: one
        wide dispatch per stage (the lane-block grid replaces the chunk
        loop), and the host Stage 2 solves B parallel length-P systems.
        """
        m, sizes = plan.m, plan.sizes
        wide_stage1, wide_stage3 = jitted_wide_stages(m, self.backend)

        t0 = time.perf_counter()
        ops = layout_mod.interleave_operands_jit(dl, d, du, b, sizes=sizes, m=m)
        c = wide_stage1(*ops)
        # Block only when the host needs the reduced rows (D2H analogue).
        red = [
            np.asarray(getattr(c, f))
            for f in ("red_dl", "red_d", "red_du", "red_b")
        ]  # (P, B) each
        t1 = time.perf_counter()

        # ---- Stage 2: host-side reduced solve, batched over the B lanes.
        s = thomas_numpy(*(r.T for r in red)).T
        t2 = time.perf_counter()

        xw = wide_stage3(c, jnp.asarray(s, dtype=c.y.dtype))
        x = np.asarray(layout_mod.deinterleave_jit(xw, sizes=sizes, m=m))
        t3 = time.perf_counter()

        timing = ChunkTiming(
            num_chunks=plan.num_chunks,
            t_stage1_ms=(t1 - t0) * 1e3,
            t_stage2_ms=(t2 - t1) * 1e3,
            t_stage3_ms=(t3 - t2) * 1e3,
            t_total_ms=(t3 - t0) * 1e3,
            n=plan.total_size,
            layout="interleaved",
        )
        return x, timing


def _stage3_with_ghost(
    stage3_fn: Callable, coeffs: Any, s_chunk: Any, s_left_edge: Any
) -> Any:
    """Run stage 3 on a chunk whose left neighbour lives in another chunk.

    ``partition_stage3`` derives s_{p-1} by shifting within the chunk, so the
    true left edge is spliced in by prepending a zeroed ghost block whose
    interface unknown is the neighbouring chunk's last s; the ghost's own rows
    are dropped from the output.
    """
    ghost = partition.PartitionCoeffs(
        y=jnp.zeros_like(coeffs.y[..., :1, :]),
        v=jnp.zeros_like(coeffs.v[..., :1, :]),
        w=jnp.zeros_like(coeffs.w[..., :1, :]),
        red_dl=jnp.zeros_like(coeffs.red_dl[..., :1]),
        red_d=jnp.zeros_like(coeffs.red_d[..., :1]),
        red_du=jnp.zeros_like(coeffs.red_du[..., :1]),
        red_b=jnp.zeros_like(coeffs.red_b[..., :1]),
    )
    padded = partition.PartitionCoeffs(
        *[jnp.concatenate([g, c], axis=-2 if c.ndim > s_chunk.ndim else -1)
          for g, c in zip(ghost, coeffs)]
    )
    s_padded = jnp.concatenate([s_left_edge, s_chunk], axis=-1)
    x = stage3_fn(padded, s_padded)
    m = coeffs.y.shape[-1] + 1
    return x[..., m:]  # drop the ghost block


# ------------------------------------------------------- the fused executor --
# Serialises fused AOT compiles: the donated-buffer warning suppression uses
# warnings.catch_warnings(), whose save/restore of the global filter list is
# not thread-safe under concurrent compiles.
_COMPILE_LOCK = threading.Lock()


def _canonical_operand(a: Any) -> Any:
    """Host operands in jax's canonical dtype (device arrays already are)."""
    if isinstance(a, np.ndarray):
        cd = jax.dtypes.canonicalize_dtype(a.dtype)
        if a.dtype != cd:
            return a.astype(cd)
    return a


def _trim_halo(c: partition.PartitionCoeffs, nb: int) -> partition.PartitionCoeffs:
    """Drop the halo block's rows: its reduced row belongs to the next chunk
    (which recomputes it as an owner), and its spikes only exist to close the
    owner rows' right-neighbour references."""
    return partition.PartitionCoeffs(
        y=c.y[..., :nb, :],
        v=c.v[..., :nb, :],
        w=c.w[..., :nb, :],
        red_dl=c.red_dl[..., :nb],
        red_d=c.red_d[..., :nb],
        red_du=c.red_du[..., :nb],
        red_b=c.red_b[..., :nb],
    )


def _sharded_fused_callable(
    plan: SolvePlan,
    backend: StageBackend,
    mesh_devices: Sequence[Any],
) -> Callable:
    """The sharded system-major trace: stage 1 + stage 3 under ``shard_map``.

    The fused block axis shards contiguously over the mesh's ``"chunks"``
    axis (one shard-aligned span per device, ``plan.shards`` devices). The
    only cross-device traffic is what the algorithm structurally requires:

    * one ``ppermute`` halo exchange — each shard sends its *first* block's
      operands to the previous shard, closing the right-neighbour reference
      of every span's last reduced row;
    * one ``all_gather`` of the per-shard reduced rows, after which every
      device runs the replicated Stage-2 solve locally and slices
      out its own interface unknowns — the "scatter" is a local
      ``dynamic_slice`` of the replicated solution, not a collective.

    The last shard's halo arrives as ``ppermute`` zeros and is patched into
    an exact identity block (``dl=0, d=1, du=0, b=0`` → spikes are exact
    zeros), which reproduces the unsharded trace's end-of-axis zero-pad
    convention bit for bit. In-shard chunking follows
    ``plan.local_chunk_bounds`` — the same static loop on every device.
    """
    m = plan.m
    num_shards = plan.shards
    bps = plan.blocks_per_shard
    local_bounds = plan.local_chunk_bounds
    stage1, _ = jitted_stages(m, backend)
    stage3_ghost = jitted_stage3_ghost(backend)
    reduced_solve = backend.make_reduced_solve(m)

    def per_shard(dl: Any, d: Any, du: Any, b: Any) -> Any:
        idx = jax.lax.axis_index(MESH_AXIS_CHUNKS)
        perm = [(i, i - 1) for i in range(1, num_shards)]
        heads = [a[:m] for a in (dl, d, du, b)]
        with jax.named_scope(spans.HALO):
            halo = [jax.lax.ppermute(a, MESH_AXIS_CHUNKS, perm) for a in heads]
        # ppermute delivers zeros to the shard nobody sends to (the last):
        # patch its halo diagonal to 1 so the halo is an exact identity
        # block, matching the unsharded end-of-axis convention exactly.
        halo[1] = jnp.where(
            idx == num_shards - 1, jnp.ones_like(halo[1]), halo[1]
        )
        ext = [jnp.concatenate([a, h]) for a, h in zip((dl, d, du, b), halo)]

        coeffs = []
        for lo, hi in local_bounds:
            def sl(a: Any, lo: int = lo, hi: int = hi) -> Any:
                # every local chunk has a halo block in ext (the in-shard
                # next block, or the exchanged/patched halo for the last)
                return jax.lax.slice_in_dim(a, lo * m, (hi + 1) * m, axis=-1)

            chunk = [sl(a) for a in ext]
            with jax.named_scope(spans.STAGE1):
                c = stage1(*chunk)
            coeffs.append(_trim_halo(c, hi - lo))
        red_local = [
            jnp.concatenate([getattr(c, f) for c in coeffs], axis=-1)
            if len(coeffs) > 1
            else getattr(coeffs[0], f)
            for f in ("red_dl", "red_d", "red_du", "red_b")
        ]
        with jax.named_scope(spans.REDUCED_GATHER):
            red = [
                jax.lax.all_gather(r, MESH_AXIS_CHUNKS, tiled=True)
                for r in red_local
            ]
        with jax.named_scope(spans.STAGE2):
            s = reduced_solve(*red)  # replicated (P,) solve on every device

        base = idx * bps
        outs = []
        for (lo, hi), c in zip(local_bounds, coeffs):
            s_chunk = jax.lax.dynamic_slice_in_dim(s, base + lo, hi - lo, axis=-1)
            if lo == 0:
                # shard 0's first chunk has no left neighbour; elsewhere the
                # (clamped) slice start base - 1 is exact for every idx > 0.
                s_left = jnp.where(
                    idx == 0,
                    jnp.zeros_like(s[..., :1]),
                    jax.lax.dynamic_slice_in_dim(
                        s, jnp.maximum(base - 1, 0), 1, axis=-1
                    ),
                )
            else:
                s_left = jax.lax.dynamic_slice_in_dim(
                    s, base + lo - 1, 1, axis=-1
                )
            with jax.named_scope(spans.STAGE3):
                outs.append(stage3_ghost(c, s_chunk, s_left))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)

    mesh = mesh_for(mesh_devices, MESH_AXIS_CHUNKS)
    pspec = PartitionSpec(MESH_AXIS_CHUNKS)
    return shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(pspec,) * 4,
        out_specs=pspec,
        check_vma=False,
    )


def _fused_callable(
    plan: SolvePlan,
    backend: StageBackend,
    donate: bool,
    avals: Sequence[jax.ShapeDtypeStruct],
    layout: str = "system-major",
    mesh_devices: Optional[Sequence[Any]] = None,
) -> Tuple[Callable, str]:
    """Trace + AOT-compile the whole three-stage solve for ``plan``; return
    the executable and the name of the Stage-2 implementation traced in.

    The chunk structure is baked in from the (static) plan: stage 1 slices
    every chunk + halo out of the fused operands via ``lax.slice`` inside the
    trace, the reduced rows are concatenated and solved ON DEVICE
    (``backend.make_reduced_solve(m)``), and stage 3 splices each chunk's
    ghost block in-trace. With ``donate=True`` the four diagonals are donated
    to XLA (``donate_argnums=(0, 1, 2, 3)``), so the solve can reuse their
    buffers in place — callers passing device arrays give up ownership.

    ``layout="interleaved"`` traces the lane-major pipeline instead: the
    interleave gather, wide stage 1, wide (B-parallel) reduced solve, wide
    stage 3 and the deinterleave gather all live inside the one executable —
    callers still hand over (and donate) the fused 1-D operands and receive
    the fused 1-D solution; the transposed layout never escapes. The plan's
    chunk partition does not apply on this path (the wide grid is the
    parallel axis); the plan still keys the plan/executable caches.

    ``mesh_devices`` (a device tuple) shards the trace across a 1-D mesh:
    on the system-major layout the fused block axis shards over a
    ``"chunks"`` axis of ``plan.shards`` devices
    (:func:`_sharded_fused_callable`); on the interleaved layout the lane
    axis shards over a ``"batch"`` axis — the wide pipeline needs no
    collectives at all (each device owns whole systems), so only the
    interleave/deinterleave gathers bracket the ``shard_map`` region.
    ``None`` (the default) is the single-device trace, unchanged.

    A ``plan.periodic`` trace solves cyclic systems: the stages wrap round
    each system's block axis and the cyclic reduced system is solved by
    :func:`repro.core.tridiag.partition.cyclic_solve` over the backend's
    plain reduced solve. On the interleaved layout that is the wide pipeline
    with both flags; otherwise the fused operands are viewed as ``(B, n)``
    and run through the batched stages, one system a row, whole.

    Compilation happens HERE (``jit(...).lower(*avals).compile()``), not at
    first call: only one of the four donated buffers can back the single
    output, so XLA warns "Some donated buffers were not usable" once per
    compile — doing the compile under a scoped ``catch_warnings`` keeps that
    expected message out of callers' logs without mutating the process-wide
    warning filters (user code jitting its own donating functions still
    sees its own diagnostics).
    """
    m = plan.m
    dtype = avals[1].dtype
    periodic = plan.periodic

    if layout == "interleaved":
        sizes = plan.sizes
        lanes = len(sizes) // (len(mesh_devices) if mesh_devices else 1)
        # A periodic solve stacks its two right-hand sides on the lanes.
        lanes *= 2 if periodic else 1
        stage2 = backend.wide_reduced_solve_impl((max(sizes) // m, lanes), dtype)
        wide_stage1, wide_stage3 = jitted_wide_stages(m, backend, periodic)
        wide_reduced = backend.make_wide_reduced_solve()
        if periodic:
            wide_reduced = partial(
                partition.cyclic_solve, wide_reduced, axis=0,
                update=backend.periodic_update,
            )

        def wide_pipeline(*ops: Any) -> Any:
            with jax.named_scope(spans.STAGE1):
                c = wide_stage1(*ops)
            with jax.named_scope(spans.STAGE2):
                s = wide_reduced(c.red_dl, c.red_d, c.red_du, c.red_b)
            with jax.named_scope(spans.STAGE3):
                return wide_stage3(c, s)

        if mesh_devices is not None:
            lane_spec = PartitionSpec(None, None, MESH_AXIS_BATCH)
            wide_pipeline = shard_map(
                wide_pipeline,
                mesh=mesh_for(mesh_devices, MESH_AXIS_BATCH),
                in_specs=(lane_spec,) * 4,
                out_specs=lane_spec,
                check_vma=False,
            )

        def fused(dl: Any, d: Any, du: Any, b: Any) -> Any:
            with jax.named_scope(spans.INTERLEAVE):
                ops = layout_mod.interleave_operands(dl, d, du, b, sizes, m)
            xw = wide_pipeline(*ops)
            with jax.named_scope(spans.DEINTERLEAVE):
                return layout_mod.deinterleave(xw, sizes, m)

    elif periodic:
        bsz, n = plan.batch, plan.sizes[0]
        # the two right-hand sides stacked on the batch axis
        stage2 = backend.reduced_solve_impl((2 * bsz, n // m), dtype)
        stage1, stage3 = jitted_stages(m, backend, periodic=True)
        reduced_solve = partial(
            partition.cyclic_solve, backend.make_reduced_solve(m),
            update=backend.periodic_update,
        )

        def fused(dl: Any, d: Any, du: Any, b: Any) -> Any:
            with jax.named_scope(spans.STAGE1):
                c = stage1(*(a.reshape(bsz, n) for a in (dl, d, du, b)))
            with jax.named_scope(spans.STAGE2):
                s = reduced_solve(c.red_dl, c.red_d, c.red_du, c.red_b)
            with jax.named_scope(spans.STAGE3):
                return stage3(c, s).reshape(bsz * n)

    elif mesh_devices is not None:
        stage2 = backend.reduced_solve_impl((plan.num_blocks,), dtype)
        fused = _sharded_fused_callable(plan, backend, mesh_devices)

    else:
        stage2 = backend.reduced_solve_impl(
            tuple(avals[1].shape[:-1]) + (plan.num_blocks,), dtype
        )
        stage1, _ = jitted_stages(m, backend)
        stage3_ghost = jitted_stage3_ghost(backend)
        reduced_solve = backend.make_reduced_solve(m)

        def fused(dl: Any, d: Any, du: Any, b: Any) -> Any:
            coeffs = []
            for (lo, hi), (_, hi_halo) in zip(plan.chunk_bounds, plan.halo_bounds):
                def sl(a: Any, lo: int = lo, hi_halo: int = hi_halo) -> Any:
                    return jax.lax.slice_in_dim(a, lo * m, hi_halo * m, axis=-1)

                chunk = [sl(a) for a in (dl, d, du, b)]
                with jax.named_scope(spans.STAGE1):
                    c = stage1(*chunk)
                coeffs.append(_trim_halo(c, hi - lo))
            red = [
                jnp.concatenate([getattr(c, f) for c in coeffs], axis=-1)
                if len(coeffs) > 1
                else getattr(coeffs[0], f)
                for f in ("red_dl", "red_d", "red_du", "red_b")
            ]
            with jax.named_scope(spans.STAGE2):
                s = reduced_solve(*red)
            outs = []
            for (lo, hi), c in zip(plan.chunk_bounds, coeffs):
                s_chunk = jax.lax.slice_in_dim(s, lo, hi, axis=-1)
                s_left_edge = (
                    jnp.zeros_like(s[..., :1])
                    if lo == 0
                    else jax.lax.slice_in_dim(s, lo - 1, lo, axis=-1)
                )
                with jax.named_scope(spans.STAGE3):
                    outs.append(stage3_ghost(c, s_chunk, s_left_edge))
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)

    if not donate:
        return jax.jit(fused), stage2
    jitted = jax.jit(fused, donate_argnums=(0, 1, 2, 3))
    # catch_warnings mutates the process-global filter list, so concurrent
    # compiles must not interleave with it (a racing restore would leak the
    # warning or clobber another thread's filters). _COMPILE_LOCK serialises
    # only the compile itself — cache lookups under _CACHE_LOCK stay free.
    with _COMPILE_LOCK, warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return jitted.lower(*avals).compile(), stage2


class FusedExecutor:
    """Single-dispatch execution of a :class:`SolvePlan`: the whole solve is
    one compiled XLA program per ``(plan, backend, dtypes, batch-shape)``.

    Where :class:`PlanExecutor` dispatches each chunk from a Python loop and
    round-trips through the host for the Stage-2 reduced solve (the paper's
    CPU stage — which is what makes its phase breakdown measurable on the
    host clock), this executor trades that for latency: zero host
    round-trips between operand hand-off and solution split, one dispatch
    regardless of chunk count. The returned :class:`ChunkTiming` therefore
    carries only ``t_total_ms`` (use the staged path for the Eq.-5
    campaigns). Per-phase times are observable in a profiler trace instead:
    the executable tags each stage's device ops with a named scope, and
    :meth:`execute` emits host spans for each call's lookup, launch, wait and
    fetch (:mod:`repro.core.tridiag.spans`).

    ``donate=True`` (default) donates the four diagonals to the executable;
    numpy operands are copied to device per call (always safe to reuse),
    device-array operands are CONSUMED — re-using one afterwards raises
    jax's donated-buffer error. Pass ``donate=False`` (or dispatch staged)
    to keep device operands alive.

    ``layout`` ("system-major" | "interleaved" | "auto", default "auto")
    picks the operand layout traced into the executable; "auto" interleaves
    flat fused batches of ≥ `layout.AUTO_INTERLEAVE_MIN_BATCH` systems *per
    shard* (see :func:`repro.core.tridiag.layout.resolve_layout`). The
    resolved layout is part of the executable-cache key — distinct layouts
    never share an executable.

    ``mesh`` (any :func:`repro.parallel.solver.resolve_mesh_devices` spec;
    default ``None``) shards the traced solve across a 1-D device mesh:
    system-major executables shard the fused block axis over ``plan.shards``
    devices (so pass a shard-aligned plan, ``build_plan(..., shards=...)``),
    interleaved executables shard the lane axis over the largest device
    count dividing the batch. Only 1-D fused operands shard (extra leading
    batch dims fall back to the single-device trace), and ``mesh=None``
    traces bit-identically to today's path. The mesh signature of the
    devices actually used joins the executable-cache key, so sharded and
    unsharded executables (or different device sets) never collide.

    Executables are cached in the module-level LRU (`executable_cache_stats`)
    under `_CACHE_LOCK`, so sessions can hit it from caller + worker threads.
    """

    def __init__(
        self,
        backend: BackendLike = None,
        *,
        donate: bool = True,
        layout: str = "auto",
        mesh: Any = None,
    ) -> None:
        self.backend = resolve_backend(backend)
        self.donate = donate
        if layout not in layout_mod.LAYOUTS:
            raise ValueError(
                f"layout must be one of {layout_mod.LAYOUTS}, got {layout!r}"
            )
        self.layout = layout
        self.mesh_devices = resolve_mesh_devices(mesh)

    def _shard_devices(
        self, plan: SolvePlan, layout: str, lead_ndim: int
    ) -> Optional[Tuple[Any, ...]]:
        """The devices this executable shards over (None = single-device)."""
        if self.mesh_devices is None or lead_ndim != 0:
            return None
        if layout == "interleaved":
            lanes = shard_count(len(plan.sizes), len(self.mesh_devices))
            return self.mesh_devices[:lanes] if lanes > 1 else None
        if 1 < plan.shards <= len(self.mesh_devices):
            return self.mesh_devices[: plan.shards]
        return None

    def _executable(
        self, plan: SolvePlan, ops: Sequence
    ) -> Tuple[Callable, str, str]:
        """The cached ``(executable, layout, stage2)`` for these operands."""
        lead_ndim = ops[1].ndim - 1
        batch_shards = (
            shard_count(len(plan.sizes), len(self.mesh_devices))
            if self.mesh_devices is not None and lead_ndim == 0
            else 1
        )
        layout = resolve_layout(
            self.layout,
            plan.sizes,
            plan.m,
            fused=True,
            lead_ndim=lead_ndim,
            batch_shards=batch_shards,
        )
        shard_devices = self._shard_devices(plan, layout, lead_ndim)
        key = (
            plan,
            self.backend,
            self.donate,
            layout,
            mesh_signature(shard_devices),
            tuple(np.dtype(jax.dtypes.canonicalize_dtype(a.dtype)).name for a in ops),
            tuple(a.shape[:-1] for a in ops),
        )
        with _CACHE_LOCK:
            entry = _EXEC_CACHE.get(key)
            if entry is not None:
                _EXEC_CACHE.move_to_end(key)
                _EXEC_STATS["hits"] += 1
                return entry
            _EXEC_STATS["misses"] += 1
        # Build (trace + compile) outside the lock: compilation is the
        # expensive part, and a racing builder is harmless (first one in
        # the cache wins; both executables are equivalent).
        avals = [
            jax.ShapeDtypeStruct(a.shape, jax.dtypes.canonicalize_dtype(a.dtype))
            for a in ops
        ]
        if layout == "interleaved":
            levels = 0
        else:
            reduced = (
                (2 * plan.batch, plan.sizes[0] // plan.m)
                if plan.periodic
                else tuple(ops[1].shape[:-1]) + (plan.num_blocks,)
            )
            levels = self.backend.reduced_solve_levels(reduced, avals[1].dtype, plan.m)
        with jax.profiler.TraceAnnotation(
            spans.COMPILE, layout=layout, rows=plan.total_size, periodic=plan.periodic
        ) as span:
            fn, stage2 = _fused_callable(
                plan, self.backend, self.donate, avals, layout, shard_devices
            )
            span.set_metadata(stage2=stage2, stage2_levels=levels)
        entry = (fn, layout, stage2)
        with _CACHE_LOCK:
            existing = _EXEC_CACHE.get(key)
            if existing is not None:
                return existing
            if _EXEC_CACHE_CAPACITY > 0:
                _EXEC_CACHE[key] = entry
                while len(_EXEC_CACHE) > _EXEC_CACHE_CAPACITY:
                    _EXEC_CACHE.popitem(last=False)
                    _EXEC_STATS["evictions"] += 1
        return entry

    def execute(
        self,
        plan: SolvePlan,
        dl: Any,
        d: Any,
        du: Any,
        b: Any,
    ) -> Tuple[np.ndarray, ChunkTiming]:
        with jax.profiler.TraceAnnotation(spans.LOOKUP):
            ops = [
                a if isinstance(a, (np.ndarray, jax.Array)) else np.asarray(a)
                for a in (dl, d, du, b)
            ]
            # The AOT-compiled executable is strict about argument dtypes;
            # mirror jit's canonicalization up front (a no-op unless e.g.
            # fp64 operands arrive while x64 is disabled).
            ops = [_canonical_operand(a) for a in ops]
            n = ops[1].shape[-1]
            if n != plan.total_size:
                raise ValueError(
                    f"operands have {n} rows but the plan lays out {plan.total_size}"
                )
            if plan.periodic and ops[1].ndim != 1:
                raise ValueError("periodic plans take the fused 1-D operands")
            self.backend.check_dtype(ops[1].dtype)
            fn, layout, stage2 = self._executable(plan, ops)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(spans.LAUNCH):
            out = fn(*ops)
            # Queue the device-to-host copy behind the solve now, as
            # np.asarray alone would: waiting first and copying after would
            # add a host round trip to every call.
            out.copy_to_host_async()
        # The wait is the device's time, the fetch what is left of the copy.
        with jax.profiler.TraceAnnotation(spans.WAIT):
            out.block_until_ready()
        with jax.profiler.TraceAnnotation(spans.FETCH):
            x = np.asarray(out)
        t1 = time.perf_counter()
        return x, ChunkTiming(
            num_chunks=plan.num_chunks,
            t_stage1_ms=0.0,
            t_stage2_ms=0.0,
            t_stage3_ms=0.0,
            t_total_ms=(t1 - t0) * 1e3,
            n=int(n),
            layout=layout,
            stage2=stage2,
            periodic=plan.periodic,
        )
