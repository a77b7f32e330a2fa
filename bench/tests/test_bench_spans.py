"""The span reduction on a small synthetic trace with known answers, and
on a trace recorded on a TPU v5e; the span names agree with the solver's."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import spans, trace

from .test_bench_trace import DATA, DEVICE_OPS, HOST, MAIN

# Times in µs, inside test_bench_trace's two call spans [0, 70) and
# [90, 170). The first call compiles inside its executable lookup; a lookup
# between the calls lies outside both and does not count.
PROGRAM = [
    ("tridiag.solve", 1, 69),
    ("tridiag.fuse", 1, 5),
    ("tridiag.lookup", 5, 12),
    ("tridiag.compile", 6, 10),
    ("tridiag.launch", 12, 20),
    ("tridiag.wait", 20, 60),
    ("tridiag.fetch", 60, 66),
    ("tridiag.split", 66, 69),
    ("tridiag.lookup", 75, 80),
    ("tridiag.solve", 91, 168),
    ("tridiag.fuse", 91, 93),
    ("tridiag.lookup", 93, 95),
    ("tridiag.launch", 95, 100),
    ("tridiag.wait", 100, 160),
    ("tridiag.fetch", 160, 165),
    ("tridiag.split", 165, 168),
]
# The op before the Stage-1 kernel is an interleave, the op that overlaps it
# a deinterleave; the kernel carries its stage's scope.
SCOPES = {
    "%copy.1 = f32[8] copy(%_stage1_impl.1)": "jit(fused)/tridiag/interleave/transpose",
    "%fusion.2 = f32[8] fusion(...)": "jit(fused)/tridiag/deinterleave/reshape",
    "%_stage1_impl.1 = (f32[9,512]) custom-call(...)":
        "jit(fused)/tridiag/stage1/jit(_stage1_impl)/pallas_call",
}


def xspace(device_ops, host, scopes) -> str:
    """test_bench_trace's synthetic trace, with each device op in ``scopes``
    carrying its scope in the :data:`spans.SCOPE_STAT` stat of its event
    metadata, where a TPU profile keeps it."""

    def plane(pid, name, line, events, stats=None):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}

        def stat(n):
            if not stats or n not in stats:
                return ""
            return f' stats {{ metadata_id: 7 str_value: "{stats[n]}" }}'

        evs = "\n".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6} }}"
            for n, s, e in events
        )
        meta = "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}"{stat(n)} }} }}'
            for n, i in ids.items()
        )
        smeta = (
            'stat_metadata { key: 3 value { id: 3 name: "hlo_category" } } '
            f'stat_metadata {{ key: 7 value {{ id: 7 name: "{spans.SCOPE_STAT}" }} }}'
        )
        return (
            f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 0 {evs} }} {meta} {smeta} }}"
        )

    return (
        plane(1, "/device:TPU:0", "XLA Ops", device_ops, scopes)
        + plane(2, "/host:CPU", "python3", host)
        + plane(3, "/host:CPU", "main/477", MAIN)
    )


def write(tmp_path: Path, host, scopes=SCOPES) -> str:
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        ProfileData.text_proto_to_serialized_xspace(xspace(DEVICE_OPS, host, scopes))
    )
    return str(path)


def test_split_synthetic(tmp_path: Path):
    split = spans.reduce(spans.load(write(tmp_path, HOST + PROGRAM)))
    us = 1000
    assert split.calls == 2
    assert split.call_ns == 150 * us
    assert split.span_ns == {
        "tridiag.fuse": 6 * us,
        "tridiag.lookup": 5 * us,  # 7 + 2, less the 4 of the compile
        "tridiag.compile": 4 * us,
        "tridiag.launch": 13 * us,
        "tridiag.wait": 100 * us,
        "tridiag.fetch": 11 * us,
        "tridiag.split": 6 * us,
    }
    got = spans.summary(split)
    assert got["fuse_split_ms"] == pytest.approx(0.006)
    assert got["lookup_ms"] == pytest.approx(0.0025)
    assert got["launch_ms"] == pytest.approx(0.0065)
    assert got["wait_ms"] == pytest.approx(0.05)
    assert got["fetch_ms"] == pytest.approx(0.0055)
    assert got["compile_ms"] == pytest.approx(0.002)
    # the interleave [10, 20) and the deinterleave [40, 60), per call
    assert got["layout_gather_ms"] == pytest.approx(0.015)
    assert got["covered_pct"] == pytest.approx(100 * 141 / 150)


def test_split_without_program_spans_or_scopes(tmp_path: Path):
    """The parent's program: no span, no scope; nothing to read."""
    assert spans.reduce(spans.load(write(tmp_path, HOST, scopes={}))) is None
    split = spans.reduce(spans.load(write(tmp_path, HOST + PROGRAM, scopes={})))
    assert split.gather_ms is None
    assert split.per_call_ms("tridiag.wait") == pytest.approx(0.05)


def test_program_spans_leave_the_trace_reduction_as_it_was(tmp_path: Path):
    without = trace.reduce(trace.load(write(tmp_path, HOST)))
    with_spans = trace.reduce(trace.load(write(tmp_path, HOST + PROGRAM)))
    assert with_spans.busy_ns == without.busy_ns
    assert with_spans.class_ns == without.class_ns
    assert with_spans.host_ns_per_call == without.host_ns_per_call
    assert with_spans.call_busy_ns == without.call_busy_ns
    # only the idle gaps' names change: the solver's spans now name them
    assert sum(with_spans.idle_gaps.values()) == sum(without.idle_gaps.values())
    assert "call" in without.idle_gaps
    assert "call" not in with_spans.idle_gaps
    assert with_spans.idle_gaps["harness"] == without.idle_gaps["harness"]
    assert {"call/tridiag.lookup", "call/tridiag.wait"} <= set(with_spans.idle_gaps)


def test_span_names_are_the_solvers():
    # imported here: collecting bench/ must not import JAX (conftest.py)
    from repro.core.tridiag import spans as solver

    assert spans.HOST_SPANS == (
        solver.FUSE, solver.LOOKUP, solver.LAUNCH, solver.WAIT, solver.FETCH,
        solver.SPLIT, solver.COMPILE,
    )
    assert set(spans.HOST_SPANS) <= set(solver.HOST_SPANS)
    assert spans.GATHER_SCOPES == (solver.INTERLEAVE, solver.DEINTERLEAVE)


def test_split_recorded_v5e_trace():
    """A trace recorded on one TPU v5e with the spans and scopes in place:
    two 40,000-row solves (Thomas kernel Stage 2) and two 998 x 1,000
    batched solves (interleaved layout), each in a ``bench.call`` span."""
    path = str(DATA / "v5e_spans.xplane.pb")
    got = spans.summary(spans.reduce(spans.load(path)))
    assert got["calls"] == 4
    for name in ("fuse_split_ms", "lookup_ms", "launch_ms", "wait_ms", "fetch_ms",
                 "layout_gather_ms"):
        assert got[name] is not None and got[name] > 0, name
    assert got["compile_ms"] is None
    assert got["covered_pct"] >= 90
    # the device ops carry their stage's scope, and the trace reduction
    # names the idle gaps after the solver's spans
    scopes = set(spans.op_scopes(path)[0].values())
    for scope in ("tridiag/stage1", "tridiag/stage2", "tridiag/stage3"):
        assert any(f"/{scope}/" in s for s in scopes), scope
    gaps = trace.reduce(trace.load(path)).idle_gaps
    assert "call/tridiag.wait" in gaps and "call" not in gaps
