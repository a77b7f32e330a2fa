"""One run of one cell: set-up, the measured window, the check, the result.

:func:`run_cell` does everything after the look for a chip, so that tests
can drive a whole run on the CPU with the solver replaced underneath.
"""

from __future__ import annotations

import contextlib
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import systems
from bench import trace as trace_mod
from bench.manifest import BENCH_DIR, Manifest

Verb = Callable[..., np.ndarray]


@dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: str
    config: dict
    traffic: dict
    peaks: dict
    shape: Tuple[int, ...] = ()
    #: bytes any implementation must move per row of ``shape`` (the kind's)
    least_bytes_per_row: int = 0
    setup_s: float = 0.0
    call_s: List[float] = field(default_factory=list)
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    stats_before: Optional[dict] = None
    stats_after: Optional[dict] = None
    reduction: Optional[trace_mod.Reduction] = None

    @property
    def completed(self) -> int:
        return self.attempted - self.failed

    @property
    def rows_per_call(self) -> int:
        return int(np.prod(self.shape))


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length
    (Li's Algorithm L): the generator is drawn only when an item is kept,
    so a window of short calls pays almost nothing for it."""

    def __init__(self, k: int, rng: np.random.Generator) -> None:
        self.k = k
        self.rng = rng
        self.items: List[Tuple[int, Any]] = []
        self.w = math.exp(math.log(self._u()) / k)
        self.next = k + self._skip()

    def _u(self) -> float:
        return float(self.rng.random()) or 1e-300

    def _skip(self) -> int:
        return int(math.floor(math.log(self._u()) / math.log1p(-self.w)))

    def offer(self, i: int, item: Any) -> None:
        if i < self.k:
            self.items.append((i, item))
        elif i == self.next:
            self.items[int(self.rng.integers(self.k))] = (i, item)
            self.w *= math.exp(math.log(self._u()) / self.k)
            self.next += self._skip() + 1


def solver_config(config: dict) -> Any:
    from repro.api import SolverConfig

    return SolverConfig(dtype=np.dtype(config["dtype"]).type, **config["solver"])


def closed_loop(
    verb: Verb,
    pool: List[systems.Operands],
    seconds: float,
    keep: Reservoir,
    run: Run,
    profile: Optional[Tuple[float, Callable[[], None]]] = None,
) -> None:
    """One call in flight at a time, cycling the pool, until ``seconds``
    have passed; the window ends when the last call started inside it
    returns. Every call's wall time is kept; outputs go to ``keep``.
    ``profile`` is ``(seconds, stop)``: the profiler runs from the window's
    start and ``stop`` is called after the first call that ends past that
    many seconds."""
    annotate = contextlib.nullcontext
    if profile is not None:
        import jax

        def annotate(i: int):  # type: ignore[misc]
            return jax.profiler.TraceAnnotation(trace_mod.CALL_SPAN, call=i)

    t_start = time.perf_counter()
    deadline = t_start + seconds
    t1 = t_start
    i = 0
    while True:
        t0 = time.perf_counter()
        if t0 >= deadline:
            break
        try:
            with annotate(i):
                x = verb(*pool[i % len(pool)])
        except Exception as e:  # a failed call counts as missing
            if run.failed == 0:
                print(f"bench: call {i} failed: {e!r}", file=sys.stderr)
            run.failed += 1
            x = None
        t1 = time.perf_counter()
        run.call_s.append(t1 - t0)
        if x is not None:
            keep.offer(i, x)
        i += 1
        if profile is not None and t1 - t_start >= profile[0]:
            profile[1]()
            profile = None
            annotate = contextlib.nullcontext
    if profile is not None:
        profile[1]()
    run.attempted = i
    run.window_s = t1 - t_start


def check(
    kept: List[Tuple[int, Any]],
    pool: List[systems.Operands],
    limit: float,
    reference: Callable[..., np.ndarray],
) -> Dict[str, Any]:
    """Compare every kept output with the float64 ``reference`` of the
    operands it was computed from."""
    refs: Dict[int, np.ndarray] = {}
    worst = 0.0
    for i, x in kept:
        k = i % len(pool)
        if k not in refs:
            refs[k] = reference(*pool[k])
        worst = max(worst, systems.max_rel_err(x, refs[k]))
    return {"max_rel_err": worst, "limit": limit, "compared": len(kept)}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def device_info(chips: int) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def load_peaks(kind: str) -> dict:
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def run_cell(
    manifest: Manifest,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    peaks: dict,
    verb_for: Optional[Callable[[Any, str], Verb]] = None,
    trace_dir: Optional[str] = None,
) -> dict:
    """Set up, drive the window, check, and return the result line.

    ``verb_for(session, verb_name)`` gives the callable the window drives;
    by default the session's own verb. The control and the fault tests put
    something else in its place."""
    from repro.api import TridiagSession

    cell = manifest.workload(workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    kind = manifest.operands(config["operands"]["kind"])
    run = Run(
        cell=workload, config=config, traffic=traffic, peaks=peaks,
        shape=kind.shape(config, traffic),
        least_bytes_per_row=int(kind.least_bytes_per_row(config)),
    )
    metrics = manifest.per_layer(workload) if trace else manifest.end_to_end(workload)
    readers = {m["name"]: manifest.reader(m["name"]) for m in metrics}

    pool = systems.make_pool(kind, config, traffic, seed)
    session = TridiagSession(solver_config(config))
    verb = (verb_for or getattr)(session, traffic["verb"])
    for i in range(traffic["warm_calls"]):
        verb(*pool[i % len(pool)])
    run.stats_before = session.stats

    profile = None
    own_dir = None
    if trace:
        import jax

        own_dir = trace_dir is None
        trace_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        # Python's own calls stay untraced: the tracer would add its cost
        # to every call of the program's host path that host_ms measures.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        profile = (float(traffic["trace_seconds"]), jax.profiler.stop_trace)
    keep = Reservoir(traffic["check_sample"], np.random.default_rng([seed, 1 << 20]))
    run.setup_s = time.perf_counter() - t_process
    closed_loop(verb, pool, seconds, keep, run, profile)
    run.stats_after = session.stats
    peak = memory_peak_bytes()
    session.close()
    del session, verb

    device = device_info(cell["chips"])
    device["memory_peak_bytes"] = peak
    result: Dict[str, Any] = {}
    if trace:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        run.reduction = trace_mod.reduce(trace_mod.load(str(files[-1]))) if files else None
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        red = run.reduction
        if red is not None:
            device["busy_s"] = red.busy_ns / 1e9
            device["window_s"] = red.window_ns / 1e9
            result["breakdown"] = {
                "device_ops": trace_mod.top(red.op_ns),
                "idle_gaps": trace_mod.top(red.idle_gaps),
            }

    values = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    verdict = check(
        keep.items, pool, float(config["check"]["max_rel_err"]), kind.reference
    )
    correct = (
        run.failed == 0
        and verdict["compared"] > 0
        and verdict["max_rel_err"] <= verdict["limit"]
    )
    return {
        "correct": bool(correct),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": values,
        "device": device,
        **result,
        "check": {
            "max_rel_err": {"value": verdict["max_rel_err"], "limit": verdict["limit"]},
            "outputs_compared": {"value": verdict["compared"], "limit": 1},
            "calls_failed": {"value": run.failed, "limit": 0},
        },
    }
