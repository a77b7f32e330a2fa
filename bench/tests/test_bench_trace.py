"""The trace reduction on a small synthetic trace with known answers, and
on a trace recorded on a TPU v5e."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

# Times in µs. Device: a glue op whose operand is the Stage-1 kernel's
# output, the kernel, an op overlapping it (the union counts the overlap
# once), a while loop with two body ops nested in it, and the wide Stage-3
# kernel. Host: two call spans; inside the first a transfer, recorded on the
# runtime's main-thread line; between them nothing.
DEVICE_OPS = [
    ("%copy.1 = f32[8] copy(%_stage1_impl.1)", 10, 20),
    ("%_stage1_impl.1 = (f32[9,512]) custom-call(...)", 30, 50),
    ("%fusion.2 = f32[8] fusion(...)", 40, 60),
    ("%while.3 = (u32[]) while(...)", 100, 150),
    ("%dynamic_slice.4 = f32[1] dynamic-slice(...)", 110, 112),
    ("%dynamic_slice.4 = f32[1] dynamic-slice(...)", 120, 122),
    ("%_stage3_impl_wide.1 = f32[10,512] custom-call(...)", 150, 160),
]
HOST = [
    ("bench.call", 0, 70),
    ("bench.call", 90, 170),
]
MAIN = [("TransferToDevice", 0, 10)]


def xspace(device_ops, host) -> str:
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "\n".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6} }}"
            for n, s, e in events
        )
        meta = "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items()
        )
        return (
            f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 0 {evs} }} {meta} }}"
        )

    return plane(1, "/device:TPU:0", "XLA Ops", device_ops) + plane(
        2, "/host:CPU", "python3", host
    ) + plane(3, "/host:CPU", "main/477", MAIN) + plane(4, "/host:CPU", "other/9", MAIN)


@pytest.fixture
def synthetic(tmp_path: Path) -> Path:
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace(DEVICE_OPS, HOST)))
    return path


def test_union_and_overlap():
    merged = trace.union([(5, 9), (0, 2), (1, 3), (9, 10)])
    assert merged == [(0, 3), (5, 10)]
    assert trace.overlap(merged, 2, 6) == 2
    assert trace.overlap(merged, 11, 20) == 0


def test_op_classes():
    assert trace.op_name("%while.10 = (u32[]) while(%tuple.3)") == "while.10"
    assert trace.op_class("_stage1_impl.1") == "stage1"
    assert trace.op_class("_stage1_impl_wide.1") == "stage1"
    assert trace.op_class("_stage3_impl_wide.1") == "stage3"
    assert trace.op_class("_thomas_impl.1") == "stage2"
    assert trace.op_class("_thomas_impl_wide.1") == "stage2"
    assert trace.op_class("while.12") == "stage2"
    assert trace.op_class("copy.3") == "xla_glue"
    assert trace.op_class("get-tuple-element.5") == "xla_glue"


def test_reduce_synthetic(synthetic: Path):
    red = trace.reduce(trace.load(str(synthetic)))
    us = 1000
    assert red.calls == 2
    assert red.window_ns == 170 * us
    # busy: [10,20) + [30,60) + [100,160) = 10 + 30 + 60
    assert red.busy_ns == 100 * us
    assert red.class_ns == {
        "xla_glue": 30 * us,
        "stage1": 20 * us,
        "stage2": 50 * us,
        "stage3": 10 * us,
    }
    assert red.call_busy_ns == [40 * us, 60 * us]
    assert red.host_ns_per_call == ((70 - 40) + (80 - 60)) / 2 * us
    # idle: [0,10) under the transfer, [20,30) and [60,70) in call 1 with no
    # inner event, [70,90) between calls, [90,100) and [160,170) in call 2
    assert red.idle_gaps == {
        "call/TransferToDevice": 10 * us,
        "call": 40 * us,
        "harness": 20 * us,
    }
    assert trace.top(red.class_ns, 2) == [["stage2", 50e-6], ["xla_glue", 30e-6]]


def test_reduce_needs_calls_and_ops(tmp_path: Path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace(DEVICE_OPS, [])))
    assert trace.reduce(trace.load(str(path))) is None


def test_innermost_at_nested():
    ev = [trace.Event("a", 0, 100), trace.Event("b", 10, 20), trace.Event("c", 30, 40)]
    got = trace.innermost_at(ev, [5, 15, 25, 35, 150])
    assert [e.name if e else None for e in got] == ["a", "b", "a", "c", None]


def test_reduce_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: two 4,000-row solves (Thomas kernel
    Stage 2), one 32 x 1,000 batched solve (wide kernels), one 60,000-row
    solve (Stage 2 on the scan), each in a ``bench.call`` span."""
    red = trace.reduce(trace.load(str(DATA / "v5e_small.xplane.pb")))
    assert red.calls == 4
    assert {"stage1", "stage2", "stage3", "xla_glue"} <= set(red.class_ns)
    assert 0 < red.busy_ns < red.window_ns
    assert sum(red.class_ns.values()) >= red.busy_ns
    assert all(0 <= b <= c for b, c in zip(red.call_busy_ns, red.call_ns))
