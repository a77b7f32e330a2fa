"""Tests for the serving admission loop, driven directly on SolveEngine
through its submit/poll/flush contract: edge cases (empty flush, exact
max_batch splits, default_chunks fallback) and the deadline/mixed-size
admission path, including the acceptance comparison against the
size-segregated PR-1 baseline."""

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.core.tridiag import make_diag_dominant_system, thomas_numpy  # noqa: E402
from repro.serve.solve import (  # noqa: E402
    AdmissionPolicy,
    SolveEngine,
    SolveRequest,
)


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


def _submit(svc, rid, size, refs=None):
    dl, d, du, b, _ = make_diag_dominant_system(size, seed=rid)
    svc.submit(SolveRequest(rid, dl, d, du, b))
    if refs is not None:
        refs[rid] = thomas_numpy(dl, d, du, b)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------- edge cases ----
def test_flush_empty_service():
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=4))
    assert svc.flush() == {}
    assert svc.pending() == 0
    assert svc.stats["batches"] == 0
    assert svc.stats["per_batch"] == []


def test_queue_split_at_exactly_max_batch():
    refs = {}
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=4))
    for rid in range(4):
        _submit(svc, rid, 60, refs)
    out = svc.flush()
    assert svc.stats["batches"] == 1  # exactly one full batch, no remainder
    assert svc.stats["per_batch"][0]["systems"] == 4

    svc2 = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=4))
    for rid in range(5):
        _submit(svc2, rid, 60, refs)
    out2 = svc2.flush()
    assert svc2.stats["batches"] == 2  # 4 + 1
    assert [p["systems"] for p in svc2.stats["per_batch"]] == [4, 1]
    for rid, x in {**out, **out2}.items():
        assert _rel_err(x, refs[rid]) < 1e-11


def test_default_chunks_fallback_without_heuristic():
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=8), default_chunks=3)
    assert svc.pick_chunks(60, 4) == 3
    assert svc.pick_chunks_ragged((60, 120)) == 3
    refs = {}
    for rid, size in enumerate((60, 60, 120)):
        _submit(svc, rid, size, refs)
    svc.flush()
    # the dispatched plan really used the fallback chunk count
    assert svc.stats["per_batch"][0]["num_chunks"] == 3


def test_chunk_pricing_identical_across_entry_points():
    """Regression: the serving queue preferred ``predict_optimum_ragged``
    while ``HeuristicChunkPolicy`` always called ``predict_optimum``, so the
    same ragged batch could get a different chunk count depending on entry
    point. Both now delegate to ``plan.price_chunks``."""
    from repro.core.tridiag import HeuristicChunkPolicy

    class SplitBrainHeuristic:
        """Ragged-aware heuristic whose two methods deliberately disagree."""

        def predict_optimum(self, size):
            return 2

        def predict_optimum_ragged(self, sizes):
            return 4

    h = SplitBrainHeuristic()
    sizes = (60, 120, 60)
    svc = SolveEngine(heuristic=h, m=10, admission=AdmissionPolicy(max_batch=8))
    policy_pick = HeuristicChunkPolicy(h).num_chunks(sizes, 10)
    assert svc.pick_chunks_ragged(sizes) == policy_pick == 4
    # and the same-size special case agrees too
    assert svc.pick_chunks(60, 3) == HeuristicChunkPolicy(h).num_chunks((60,) * 3, 10)


def test_zero_chunk_heuristic_pick_cannot_kill_a_dispatch():
    """Regression: the serving queue feeds the heuristic's pick to build_plan
    as an *explicit* num_chunks (strict by contract), so a heuristic rounding
    to 0 on a tiny batch raised mid-dispatch and the already-dequeued
    requests vanished. price_chunks now clamps to >= 1 for every entry
    point."""

    class ZeroPickHeuristic:
        def predict_optimum(self, size):
            return 0

        def predict_optimum_ragged(self, sizes):
            return 0

    svc = SolveEngine(
        heuristic=ZeroPickHeuristic(), m=10, admission=AdmissionPolicy(max_batch=4)
    )
    assert svc.pick_chunks_ragged((60,)) == 1
    refs = {}
    _submit(svc, 0, 60, refs)
    out = svc.flush()  # used to raise ValueError and drop the request
    assert _rel_err(out[0], refs[0]) < 1e-11
    assert svc.stats["per_batch"][0]["num_chunks"] == 1


def test_max_batch_and_admission_conflict_is_rejected():
    """max_batch lives inside the admission policy only: there is no second
    ctor arg to conflict with it, and a bad value is refused, not ignored."""
    with pytest.raises(TypeError):
        SolveEngine(m=10, max_batch=8, admission=AdmissionPolicy(max_wait_ms=5.0))
    with pytest.raises(ValueError, match="max_batch"):
        AdmissionPolicy(max_batch=0)
    with pytest.raises(ValueError, match="max_wait_ms"):
        AdmissionPolicy(max_wait_ms=-1.0)


def test_submit_rejects_indivisible_size():
    svc = SolveEngine(m=10)
    dl, d, du, b, _ = make_diag_dominant_system(55, seed=0)
    with pytest.raises(ValueError):
        svc.submit(SolveRequest(0, dl, d, du, b))


@pytest.mark.parametrize("bad", ["dl", "du", "b"])
def test_submit_rejects_mismatched_diagonals_naming_request(bad):
    """Regression: a request whose diagonals disagree with req.size used to
    sail through submit and explode later inside the fused dispatch with an
    opaque shape error — riding in a batch of innocent neighbours. submit()
    now validates and names the offending request id."""
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=4))
    dl, d, du, b, _ = make_diag_dominant_system(60, seed=0)
    parts = {"dl": dl, "du": du, "b": b}
    parts[bad] = parts[bad][:-1]  # one short diagonal
    with pytest.raises(ValueError, match=rf"request 7: {bad} has shape"):
        svc.submit(SolveRequest(7, parts["dl"], d, parts["du"], parts["b"]))
    assert svc.pending() == 0  # never enqueued: no innocent batch poisoned

    # a 2-D d is rejected up front too (solve_batched is the (B, n) door)
    DL, D, DU, B, _ = make_diag_dominant_system(60, seed=1, batch=(2,))
    with pytest.raises(ValueError, match="request 8: d must be 1-D"):
        svc.submit(SolveRequest(8, DL, D, DU, B))


# -------------------------------------------------------- admission triggers --
def test_max_batch_admission_dispatches_on_submit():
    clock = FakeClock()
    svc = SolveEngine(
        m=10, admission=AdmissionPolicy(max_batch=2), eager=True, clock=clock
    )
    refs = {}
    _submit(svc, 0, 60, refs)
    assert svc.pending() == 1 and svc.stats["batches"] == 0
    _submit(svc, 1, 60, refs)
    assert svc.pending() == 0 and svc.stats["batches"] == 1  # trigger: max_batch
    out = svc.poll()
    assert set(out) == {0, 1}
    for rid, x in out.items():
        assert _rel_err(x, refs[rid]) < 1e-11


def test_deadline_admission_dispatches_partial_batch():
    clock = FakeClock()
    svc = SolveEngine(
        m=10,
        admission=AdmissionPolicy(max_batch=64, max_wait_ms=50.0),
        eager=True,
        clock=clock,
    )
    refs = {}
    _submit(svc, 0, 60, refs)
    _submit(svc, 1, 120, refs)
    assert svc.poll() == {}  # nothing has waited long enough
    clock.t = 0.020
    assert svc.poll() == {}  # 20 ms < 50 ms
    clock.t = 0.060
    out = svc.poll()  # oldest waited 60 ms >= 50 ms -> partial, mixed batch
    assert set(out) == {0, 1}
    assert svc.stats["batches"] == 1
    pb = svc.stats["per_batch"][0]
    assert pb["ragged"] is True and pb["systems"] == 2
    assert pb["max_wait_ms"] == pytest.approx(60.0)
    for rid, x in out.items():
        assert _rel_err(x, refs[rid]) < 1e-11


def test_mixed_sizes_do_not_wait_for_size_mates():
    """A full mixed-size FIFO prefix dispatches as one ragged batch."""
    clock = FakeClock()
    svc = SolveEngine(
        m=10, admission=AdmissionPolicy(max_batch=3), eager=True, clock=clock
    )
    refs = {}
    for rid, size in enumerate((60, 240, 120)):
        _submit(svc, rid, size, refs)
    assert svc.stats["batches"] == 1  # one ragged dispatch, no size queues
    pb = svc.stats["per_batch"][0]
    assert pb["ragged"] is True
    assert pb["sizes"] == (60, 240, 120)
    assert pb["effective_size"] == 420
    out = svc.poll()
    for rid, x in out.items():
        assert _rel_err(x, refs[rid]) < 1e-11
        # results own their data: a retained solution must not pin the whole
        # fused batch solution alive
        assert x.base is None


# --------------------------------------------- acceptance: vs PR-1 baseline --
def test_ragged_admission_beats_size_segregated_baseline():
    """A mixed-size workload dispatches in fewer batches than the PR-1
    same-size-only batcher, with per-batch latency and wait stats."""
    workload = [60, 120, 60, 120, 60, 120]  # interleaved size classes

    def run(allow_ragged):
        svc = SolveEngine(
            m=10,
            admission=AdmissionPolicy(max_batch=6, allow_ragged=allow_ragged),
            eager=True,
        )
        refs = {}
        for rid, size in enumerate(workload):
            _submit(svc, rid, size, refs)
        out = svc.flush()
        assert set(out) == set(refs)
        for rid, x in out.items():
            assert _rel_err(x, refs[rid]) < 1e-11
        return svc

    ragged = run(allow_ragged=True)
    segregated = run(allow_ragged=False)
    assert ragged.stats["batches"] == 1
    assert segregated.stats["batches"] == 2  # one per size class
    assert ragged.stats["batches"] < segregated.stats["batches"]
    # stats expose per-batch latency and queue wait for both modes
    for svc in (ragged, segregated):
        for pb in svc.stats["per_batch"]:
            assert pb["latency_ms"] > 0
            assert pb["mean_wait_ms"] >= 0
            assert pb["max_wait_ms"] >= pb["mean_wait_ms"]
    assert ragged.systems_per_sec > 0


def test_legacy_flush_contract_is_preserved():
    """No admission policy: submit only enqueues (PR-1 behaviour), flush
    drains everything and mixed sizes still fuse instead of serialising."""
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=4))
    refs = {}
    for rid, size in enumerate((60, 60, 60, 60, 60, 120, 120)):
        _submit(svc, rid, size, refs)
    assert svc.pending() == 7  # nothing dispatched eagerly
    out = svc.flush()
    assert svc.pending() == 0
    assert set(out) == set(refs)
    assert svc.stats["batches"] == 2  # [60 x4], [60, 120, 120] ragged
    assert svc.stats["per_batch"][1]["ragged"] is True
