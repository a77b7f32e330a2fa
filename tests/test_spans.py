"""The solver's profiler spans and device scopes.

Host spans are read back from a profile recorded on the CPU around each
synchronous verb and one served batch; device scopes from the ``op_name``
metadata of compiled fused executables (system-major, interleaved and
sharded). The Pallas kernels' names under these scopes are checked on a
described TPU in ``test_tpu_compile.py``.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SolveRequest, SolverConfig, TridiagSession
from repro.core.tridiag import spans
from repro.core.tridiag.plan import (
    ReferenceBackend,
    _fused_callable,
    build_plan,
    clear_executable_cache,
)

M = 10


class Span(NamedTuple):
    name: str
    start: int
    end: int
    stats: Dict[str, object]

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def record(tmp_path: Path, body: Callable[[], None]) -> List[Span]:
    """Run ``body`` under the profiler; every ``tridiag.*`` host span, by start."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        body()
    (path,) = tmp_path.rglob("*.xplane.pb")
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in spans.HOST_SPANS:
                    start = int(e.start_ns)
                    found.append(
                        Span(e.name, start, start + int(e.duration_ns), dict(e.stats))
                    )
    return sorted(found, key=lambda s: (s.start, -s.end))


def system(rng: np.random.Generator, n: int):
    dl = rng.uniform(-1, 1, n)
    du = rng.uniform(-1, 1, n)
    dl[0] = du[-1] = 0.0
    d = 4.0 + rng.uniform(0, 1, n)
    return dl, d, du, rng.uniform(-1, 1, n)


RNG = np.random.default_rng(7)
ONE = system(RNG, 400)
BATCH = tuple(np.stack(a) for a in zip(*[system(RNG, 200) for _ in range(4)]))
MANY = [system(RNG, n) for n in (100, 300, 200)]

#: verb -> (its span, the call, rows, systems)
VERBS = {
    "solve": (spans.SOLVE, lambda s: s.solve(*ONE), 400, 1),
    "solve_batched": (spans.SOLVE_BATCHED, lambda s: s.solve_batched(*BATCH), 800, 4),
    "solve_many": (spans.SOLVE_MANY, lambda s: s.solve_many(MANY), 600, 3),
}

#: The executable lookup follows the session's plan lookup, so a call
#: emits ``tridiag.lookup`` twice; every other step once, in this order.
STEPS = [
    spans.FUSE, spans.LOOKUP, spans.LOOKUP, spans.LAUNCH, spans.WAIT, spans.FETCH,
    spans.SPLIT,
]


@pytest.mark.parametrize("verb", sorted(VERBS))
def test_verb_spans_nest_in_order(tmp_path: Path, verb: str):
    name, call, rows, systems = VERBS[verb]
    # the interleaved layout for the batch, whatever its size
    config = SolverConfig(m=M, layout="interleaved" if verb == "solve_batched" else "auto")
    clear_executable_cache()
    with TridiagSession(config) as session:
        found = record(tmp_path, lambda: [call(session), call(session)])
    verbs = [s for s in found if s.name == name]
    assert len(verbs) == 2
    for k, v in enumerate(verbs):
        assert v.stats == {"rows": rows, "systems": systems}
        inner = [s for s in found if v.holds(s) and s is not v]
        steps = [s for s in inner if s.name != spans.COMPILE]
        assert [s.name for s in steps] == STEPS
        assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
        compiles = [s for s in inner if s.name == spans.COMPILE]
        # the first call compiles, inside the executor's lookup; the repeat hits
        assert len(compiles) == (1 if k == 0 else 0)
        if compiles:
            assert steps[2].holds(compiles[0])
            assert compiles[0].stats["rows"] == rows
            assert compiles[0].stats["layout"] in ("system-major", "interleaved")
            assert "stage2" in compiles[0].stats
    assert not [s for s in found if s.name == spans.BATCH]


def test_served_batch_span_holds_the_executor_spans(tmp_path: Path):
    config = SolverConfig(m=M, max_batch=4, max_wait_ms=1.0)

    def serve():
        with TridiagSession(config) as session:
            futures = [
                session.submit(SolveRequest(rid, *ONE)) for rid in (11, 12)
            ]
            for f in futures:
                f.result(timeout=60)

    found = record(tmp_path, serve)
    batches = [s for s in found if s.name == spans.BATCH]
    assert batches and sum(b.stats["systems"] for b in batches) == 2
    assert batches[0].stats["first_rid"] == 11
    for b in batches:
        inner = [s.name for s in found if b.holds(s) and s is not b]
        assert [n for n in inner if n != spans.COMPILE] == [
            spans.LOOKUP, spans.LAUNCH, spans.WAIT, spans.FETCH
        ]


def compiled_hlo(sizes, layout: str, devices=None) -> str:
    shards = len(devices) if devices else 1
    plan = build_plan(sizes, M, num_chunks=2, shards=shards)
    avals = [jax.ShapeDtypeStruct((plan.total_size,), jnp.float32)] * 4
    fn, _ = _fused_callable(plan, ReferenceBackend(), True, avals, layout, devices)
    return fn.as_text()


def scopes_in(hlo: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', hlo)
    return {s for s in spans.DEVICE_SCOPES if any(f"/{s}/" in n for n in names)}


STAGES = {spans.STAGE1, spans.STAGE2, spans.STAGE3}


def test_interleaved_executable_carries_layout_and_stage_scopes():
    # ragged sizes: the layout change is a gather, not a pure relayout
    hlo = compiled_hlo((200, 100, 300, 200), "interleaved")
    assert scopes_in(hlo) == STAGES | {spans.INTERLEAVE, spans.DEINTERLEAVE}


def test_system_major_executable_carries_stage_scopes():
    assert scopes_in(compiled_hlo(4000, "system-major")) == STAGES


def test_sharded_executable_carries_collective_scopes(multi_device_count: int):
    hlo = compiled_hlo(4000, "system-major", tuple(jax.devices()[:2]))
    assert scopes_in(hlo) == STAGES | {spans.HALO, spans.REDUCED_GATHER}
    # each collective sits under its own scope
    for op, scope in (("collective-permute", spans.HALO), ("all-gather", spans.REDUCED_GATHER)):
        lines = [ln for ln in hlo.splitlines() if f" {op}(" in ln or f" {op}-start(" in ln]
        assert lines, op
        assert all(f"/{scope}/" in ln for ln in lines), op
