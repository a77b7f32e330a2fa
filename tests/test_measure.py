"""Tests for the wall-clock measurement campaign plumbing: the per-phase
instrument `staged_timing` on single, batched and ragged operands, the Eq.-5
baseline phases coming from one coherent rep (`_measure_cell`), the
campaigns' row keys and grid, and the stage backend threaded through to the
solves they time."""

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.core.streams.measure import (  # noqa: E402
    _measure_cell,
    measure_batched_dataset,
    measure_dataset,
    measure_ragged_dataset,
    staged_timing,
)
from repro.core.tridiag.batched import fuse_systems, split_systems  # noqa: E402
from repro.core.tridiag.plan import ChunkTiming  # noqa: E402
from repro.core.tridiag.ragged import fuse_ragged, split_ragged  # noqa: E402
from repro.core.tridiag.reference import (  # noqa: E402
    make_diag_dominant_system,
    thomas_numpy,
)


def _timing(k, total, s1, s3, n=600):
    return ChunkTiming(
        num_chunks=k,
        t_stage1_ms=s1,
        t_stage2_ms=total - s1 - s3,
        t_stage3_ms=s3,
        t_total_ms=total,
        n=n,
    )


def test_measure_cell_baseline_phases_come_from_single_best_rep():
    """Regression: t_non and sum were independent minima over *different*
    baseline reps, so Eq. 5 could combine phases of mismatched runs and go
    negative. Both must come from the single best-total rep."""
    # Baseline reps: the best-total rep (10ms) has stage sum 8; a slower rep
    # (12ms) happens to have a tiny stage sum (2). The old code paired
    # t_non=10 with s=2.
    schedule = {
        1: [_timing(1, 11.0, 5.0, 5.0),   # warmup, discarded
            _timing(1, 10.0, 4.0, 4.0),   # best total, s = 8
            _timing(1, 12.0, 1.0, 1.0)],  # worse total, s = 2
        2: [_timing(2, 9.0, 3.0, 3.0),    # warmup, discarded
            _timing(2, 8.5, 3.0, 3.0),
            _timing(2, 8.5, 3.0, 3.0)],
    }

    def run(k):
        return schedule[k].pop(0)

    rows = []
    _measure_cell(rows, run, size=600, batch=None, candidates=(1, 2), reps=2)
    assert len(rows) == 2
    for row in rows:
        assert row["t_non_str"] == 10.0
        assert row["sum"] == 8.0  # the best rep's phases, not the cross-rep min
        # Eq. 5: (8.5 - 10) + (1/2)*8 = 2.5 — the mismatched pairing
        # ((8.5 - 10) + (1/2)*2 = -0.5) went negative.
        assert row["t_overhead"] == (8.5 - 10.0) + 0.5 * 8.0
        assert row["t_overhead"] >= 0.0


def test_measure_dataset_runs_on_selected_backend():
    """The campaign accepts backend= and still produces well-formed rows."""
    data = measure_dataset((120,), candidates=(1, 2), reps=1, backend="pallas")
    assert data.rows
    for row in data.rows:
        assert row["size"] == 120
        assert row["num_str"] == 2
        assert np.isfinite(row["t_overhead"])


# ------------------------------------------------------ the staged instrument --
def _operands(kind):
    """``(sizes, fused operands, split, per-system references)`` of one kind."""
    if kind == "single":
        dl, d, du, b, _ = make_diag_dominant_system(400, seed=1)
        return 400, (dl, d, du, b), lambda x: [x], [thomas_numpy(dl, d, du, b)]
    if kind == "batched":
        dl, d, du, b, _ = make_diag_dominant_system(120, seed=2, batch=(3,))
        refs = [thomas_numpy(dl[i], d[i], du[i], b[i]) for i in range(3)]
        return (120,) * 3, fuse_systems(dl, d, du, b), lambda x: split_systems(x, 3), refs
    systems = [
        make_diag_dominant_system(n, seed=3 + i)[:4] for i, n in enumerate((60, 200, 140))
    ]
    *ops, sizes = fuse_ragged(systems)
    refs = [thomas_numpy(*sy) for sy in systems]
    return sizes, tuple(ops), lambda x: split_ragged(x, sizes), refs


@pytest.mark.parametrize("num_chunks", [1, 4])
@pytest.mark.parametrize("kind", ["single", "batched", "ragged"])
def test_staged_timing_matches_thomas_with_phase_times(kind, num_chunks):
    """The instrument solves fused operands of every kind on the chunked
    plan and reports a non-zero time for each phase."""
    sizes, ops, split, refs = _operands(kind)
    x, timing = staged_timing(sizes, *ops, m=10, num_chunks=num_chunks, backend=None)
    for xi, ref in zip(split(x), refs):
        assert np.max(np.abs(xi - ref)) / np.max(np.abs(ref)) < 1e-11
    assert timing.num_chunks == num_chunks
    assert timing.t_stage1_ms > 0 and timing.t_stage2_ms > 0 and timing.t_stage3_ms > 0
    assert timing.t_total_ms >= sum(timing.phases)
    assert timing.stage2 == "host" and timing.layout == "system-major"


_ROW_KEYS = ["num_str", "rep", "size", "stage_times", "sum", "t_non_str",
             "t_overhead", "t_str"]


@pytest.mark.parametrize("campaign, keys, grid", [
    (lambda: measure_dataset((60, 120), candidates=(1, 2, 4), reps=2),
     _ROW_KEYS,
     [(60, 2, 0, None, None), (60, 2, 1, None, None), (60, 4, 0, None, None),
      (60, 4, 1, None, None), (120, 2, 0, None, None), (120, 2, 1, None, None),
      (120, 4, 0, None, None), (120, 4, 1, None, None)]),
    (lambda: measure_batched_dataset((60,), batches=(1, 3), candidates=(1, 2), reps=2),
     sorted(_ROW_KEYS + ["batch"]),
     [(60, 2, 0, 1, None), (60, 2, 1, 1, None), (60, 2, 0, 3, None),
      (60, 2, 1, 3, None)]),
    (lambda: measure_ragged_dataset([(60, 120), (30, 30, 60)], candidates=(1, 4), reps=1),
     sorted(_ROW_KEYS + ["mix"]),
     [(180, 4, 0, None, (60, 120)), (120, 4, 0, None, (30, 30, 60))]),
], ids=["single", "batched", "ragged"])
def test_campaign_rows_keep_their_keys_and_grid(campaign, keys, grid):
    """The campaigns emit the rows, keys and (size, num_str) grid they
    emitted when they timed through a session's staged verbs."""
    rows = campaign().rows
    assert all(sorted(row) == keys for row in rows)
    assert [
        (r["size"], r["num_str"], r["rep"], r.get("batch"), r.get("mix")) for r in rows
    ] == grid
    assert all(r["sum"] > 0 and r["t_str"] > 0 for r in rows)
