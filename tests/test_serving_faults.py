"""Threaded serving-fault suite: the heavy-traffic hardening contract.

The serving layer promises that under arbitrary dispatch failures and
overload, (a) no SolveFuture is ever left unresolved, (b) the worker thread
never dies while the session is open — and if it somehow does, the death is
surfaced instead of hanging callers, (c) the admission queue stays bounded
with rejections signalled immediately, and (d) per-request timeouts and
cancellation shed work before it can ride a batch. Every test here drives
real threads; fault injection goes through the engine's ``executor`` seam or
monkeypatched tail helpers.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.api import (  # noqa: E402
    QueueFullError,
    RequestCancelledError,
    RequestTimedOutError,
    SolveEngine,
    SolveRequest,
    SolverConfig,
    TridiagSession,
    WorkerDiedError,
)
from repro.core.tridiag import api as api_mod  # noqa: E402
from repro.core.tridiag.reference import (  # noqa: E402
    make_diag_dominant_system,
    thomas_numpy,
)


def _sys(n, seed):
    return make_diag_dominant_system(n, seed=seed)[:4]


def _rel_err(x, ref):
    return np.max(np.abs(np.asarray(x, np.float64) - ref)) / (
        np.max(np.abs(ref)) + 1e-30
    )


class WrappingExecutor:
    """Fault-injection seam: delay, or raise on chosen dispatch indices."""

    def __init__(self, inner, *, delay_s=0.0, fail_on=(), fail_always=False):
        self.inner = inner
        self.delay_s = delay_s
        self.fail_on = set(fail_on)
        self.fail_always = fail_always
        self.calls = 0

    def execute(self, plan, *operands):
        call = self.calls
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail_always or call in self.fail_on:
            raise RuntimeError(f"injected dispatch fault (call {call})")
        return self.inner.execute(plan, *operands)


# ------------------------------------------------- dispatch-tail guarding ---
def test_post_execute_tail_failure_fails_batch_not_worker(monkeypatch):
    """THE original bug: an exception after the solve (here: in the
    split_ragged tail) used to escape _dispatch, silently kill the worker,
    and hang every later submit forever. It must fail exactly that batch's
    futures and leave the session serving."""
    with TridiagSession(SolverConfig(m=10, max_batch=2, max_wait_ms=20.0)) as session:
        boom = RuntimeError("tail exploded after execute")

        def raising_split(x, sizes):
            raise boom

        monkeypatch.setattr(api_mod, "split_ragged", raising_split)
        f0 = session.submit(SolveRequest(0, *_sys(60, 0)))
        f1 = session.submit(SolveRequest(1, *_sys(60, 1)))
        assert f0.exception(timeout=10.0) is boom
        assert f1.exception(timeout=10.0) is boom
        monkeypatch.undo()

        # the worker survived and the session still serves
        assert session._worker.is_alive()
        dl, d, du, b = _sys(60, 2)
        f2 = session.submit(SolveRequest(2, dl, d, du, b))
        assert _rel_err(f2.result(timeout=10.0), thomas_numpy(dl, d, du, b)) < 1e-11
    assert session.stats["failed"] == 2


def test_raising_on_result_callback_fails_only_that_request():
    """Engine-level regression: a result callback that raises must fail ITS
    request via on_error and still deliver the rest of the batch — never
    escape into the caller (the session's worker loop)."""
    delivered, errored = {}, {}

    def on_result(rid, x):
        if rid == 1:
            raise ValueError("consumer exploded")
        delivered[rid] = x

    engine = SolveEngine(
        m=10, on_result=on_result, on_error=lambda rid, e: errored.update({rid: e})
    )
    for rid in range(3):
        engine.submit(SolveRequest(rid, *_sys(60, rid)))
    engine._dispatch(engine._take_group(), engine._clock())  # must not raise
    assert sorted(delivered) == [0, 2]
    assert list(errored) == [1]
    assert isinstance(errored[1], ValueError)
    assert engine.stats["failed"] == 1
    # the engine still serves
    engine.submit(SolveRequest(9, *_sys(60, 9)))
    engine._dispatch(engine._take_group(), engine._clock())
    assert 9 in delivered


def test_dispatch_fault_resolves_every_future_and_worker_survives():
    """Fault-injected solve failures: every submitted future resolves with
    the injected error (none left unresolved), the worker stays alive, and
    serving resumes once the fault clears."""
    session = TridiagSession(SolverConfig(m=10, max_batch=4, max_wait_ms=5.0))
    try:
        real = session._engine._executor
        session._engine._executor = WrappingExecutor(real, fail_always=True)
        futs = [
            session.submit(SolveRequest(i, *_sys(60, i))) for i in range(8)
        ]
        for f in futs:
            e = f.exception(timeout=10.0)
            assert isinstance(e, RuntimeError) and "injected" in str(e)
        assert session._worker.is_alive()
        assert session.pending() == 0  # nothing leaked in queue or futures

        session._engine._executor = real
        dl, d, du, b = _sys(120, 77)
        f = session.submit(SolveRequest(100, dl, d, du, b))
        assert _rel_err(f.result(timeout=10.0), thomas_numpy(dl, d, du, b)) < 1e-11
    finally:
        session.close()
    assert session.stats["failed"] == 8


def test_close_during_inflight_faulty_batch():
    """close() while a slow batch is mid-flight and about to fault: close
    returns (no hang), the batch's futures resolve with the fault, drained
    queue futures resolve too."""
    session = TridiagSession(SolverConfig(m=10, max_batch=1))
    real = session._engine._executor
    session._engine._executor = WrappingExecutor(
        real, delay_s=0.15, fail_on=(0,)
    )
    f0 = session.submit(SolveRequest(0, *_sys(60, 0)))  # faulty + slow
    f1 = session.submit(SolveRequest(1, *_sys(60, 1)))  # drains on close
    time.sleep(0.05)  # let the worker take batch 0 into flight
    t0 = time.perf_counter()
    session.close()
    assert time.perf_counter() - t0 < 10.0
    assert isinstance(f0.exception(timeout=0), RuntimeError)
    dl, d, du, b = _sys(60, 1)
    assert _rel_err(f1.result(timeout=0), thomas_numpy(dl, d, du, b)) < 1e-11
    assert session.pending() == 0


# ------------------------------------------------------ worker supervision --
def test_worker_death_fails_futures_and_next_submit_raises():
    """If the worker dies anyway (here: a fault injected into the lock-held
    queue surgery, which cannot be attributed to one batch), every
    outstanding future resolves with WorkerDiedError and the next submit
    raises it instead of enqueuing into a void."""
    session = TridiagSession(SolverConfig(m=10, max_batch=2))
    try:
        def surgery_bomb(now):
            raise RuntimeError("queue surgery bug")

        session._engine.take_due_group = surgery_bomb
        fut = session.submit(SolveRequest(0, *_sys(60, 0)))
        err = fut.exception(timeout=10.0)
        assert isinstance(err, WorkerDiedError)
        assert "queue surgery bug" in str(err)
        session._worker.join(timeout=10.0)
        assert not session._worker.is_alive()
        with pytest.raises(WorkerDiedError, match="create a new TridiagSession"):
            session.submit(SolveRequest(1, *_sys(60, 1)))
        assert session.pending() == 0
    finally:
        session.close()


# ----------------------------------------------------------- backpressure ---
def test_submit_raises_queue_full_and_try_submit_returns_none():
    cfg = SolverConfig(m=10, max_batch=64, max_queue=2)  # inf deadline: holds
    session = TridiagSession(cfg)
    try:
        futs = [session.submit(SolveRequest(i, *_sys(60, i))) for i in range(2)]
        with pytest.raises(QueueFullError, match="request 2"):
            session.submit(SolveRequest(2, *_sys(60, 2)))
        assert session.try_submit(SolveRequest(3, *_sys(60, 3))) is None
        st = session.stats
        assert st["rejected"] == 2
        assert st["queue_depth"] == 2 and st["queue_high_water"] == 2
        assert all(not f.done() for f in futs)  # admitted work untouched
    finally:
        session.close()
    assert all(f.done() for f in futs)


def test_try_submit_hammer_respects_bound_and_leaks_nothing():
    """Acceptance: submit hammer against max_queue=K with a slowed solver —
    the queue never exceeds K, rejections are immediate (try_submit → None),
    every accepted future resolves, the worker is alive at the end."""
    K, threads, per_thread = 6, 4, 30
    session = TridiagSession(
        SolverConfig(m=10, max_batch=2, max_wait_ms=1.0, max_queue=K)
    )
    try:
        session._engine._executor = WrappingExecutor(
            session._engine._executor, delay_s=0.002
        )
        accepted, rejected = [], 0
        lock = threading.Lock()

        def hammer(tid):
            nonlocal rejected
            for i in range(per_thread):
                rid = tid * per_thread + i
                fut = session.try_submit(SolveRequest(rid, *_sys(60, rid % 7)))
                with lock:
                    if fut is None:
                        rejected += 1
                    else:
                        accepted.append(fut)

        workers = [
            threading.Thread(target=hammer, args=(t,)) for t in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join()

        for fut in accepted:
            fut.result(timeout=30.0)  # raises if any dispatch failed
        st = session.stats
        assert st["queue_high_water"] <= K
        assert st["rejected"] == rejected
        assert len(accepted) + rejected == threads * per_thread
        assert st["systems"] == len(accepted)
        assert session._worker.is_alive()
        assert session.pending() == 0
    finally:
        session.close()


# -------------------------------------------------- timeouts + priorities ---
def test_per_request_timeout_fires_while_queued():
    """A queued request past its timeout_ms resolves with
    RequestTimedOutError on its own — the worker wakes for it even though
    the admission deadline (max_wait_ms=inf) would never fire."""
    session = TridiagSession(SolverConfig(m=10, max_batch=64))
    try:
        t0 = time.perf_counter()
        fut = session.submit(SolveRequest(0, *_sys(60, 0), timeout_ms=40.0))
        with pytest.raises(RequestTimedOutError, match="request 0"):
            fut.result(timeout=10.0)
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        assert elapsed_ms >= 40.0
        assert elapsed_ms < 5_000.0
        assert session.stats["timed_out"] == 1
        assert session.pending() == 0
    finally:
        session.close()


def test_expired_request_is_shed_not_batched():
    """An already-expired request never rides a dispatch: it is shed before
    the batch is taken, and the batch forms from live requests only."""
    session = TridiagSession(SolverConfig(m=10, max_batch=2))
    try:
        dead = session.submit(SolveRequest(0, *_sys(60, 0), timeout_ms=0.0))
        live = [
            session.submit(SolveRequest(rid, *_sys(60, rid)))
            for rid in (1, 2)
        ]
        for rid, f in zip((1, 2), live):
            dl, d, du, b = _sys(60, rid)
            assert _rel_err(f.result(timeout=10.0), thomas_numpy(dl, d, du, b)) < 1e-11
        assert isinstance(dead.exception(timeout=10.0), RequestTimedOutError)
        st = session.stats
        assert st["timed_out"] == 1
        assert [pb["systems"] for pb in st["per_batch"]] == [2]
    finally:
        session.close()


def test_priority_orders_admission_fifo_within():
    """Higher priority admits first; FIFO among equals (engine-level — the
    queue surgery is identical under the session)."""
    engine = SolveEngine(m=10, admission=api_mod.AdmissionPolicy(max_batch=2))
    for rid, prio in ((0, 0), (1, 0), (2, 5), (3, 5)):
        engine.submit(SolveRequest(rid, *_sys(60, rid), priority=prio))
    first = [p.req.rid for p in engine._take_group()]
    second = [p.req.rid for p in engine._take_group()]
    assert first == [2, 3]  # both priority-5, in submit order
    assert second == [0, 1]


def test_admission_deadline_follows_oldest_not_highest_priority():
    """max_wait_ms belongs to the OLDEST request even when priority
    reordering puts a newer request at the queue head."""
    clock = [0.0]
    engine = SolveEngine(
        m=10,
        admission=api_mod.AdmissionPolicy(max_batch=64, max_wait_ms=100.0),
        clock=lambda: clock[0],
    )
    engine.submit(SolveRequest(0, *_sys(60, 0), priority=0))
    clock[0] = 0.05
    engine.submit(SolveRequest(1, *_sys(60, 1), priority=9))
    # queue head is now rid 1 (newer, higher priority); the deadline must
    # still be rid 0's: 0.1s after ITS submit, i.e. 0.05s from now.
    assert engine._queue[0].req.rid == 1
    assert engine.seconds_to_next_event(0.05) == pytest.approx(0.05)
    clock[0] = 0.11
    assert engine.take_due_group(0.11) is not None


# ------------------------------------------------------------ cancellation --
def test_cancel_before_admission_sheds_after_admission_noop():
    session = TridiagSession(SolverConfig(m=10, max_batch=64))  # inf deadline
    try:
        fut = session.submit(SolveRequest(0, *_sys(60, 0)))
        assert fut.cancel() is True
        assert fut.cancelled()
        with pytest.raises(RequestCancelledError, match="request 0"):
            fut.result(timeout=0)
        assert fut.cancel() is False  # idempotent: already resolved
        assert session.stats["cancelled"] == 1
        assert session.pending() == 0

        # after admission: a future that already resolved cannot be cancelled
        dl, d, du, b = _sys(60, 1)
        f2 = session.submit(SolveRequest(1, dl, d, du, b))
        f3 = session.submit(SolveRequest(2, *_sys(60, 2)))
        session.close()  # drains: both dispatch
        assert f2.cancel() is False
        assert not f2.cancelled()
        assert _rel_err(f2.result(timeout=0), thomas_numpy(dl, d, du, b)) < 1e-11
        assert f3.done()
    finally:
        session.close()


def test_cancel_while_batch_in_flight_returns_false():
    """Once the worker has taken the batch, cancel() is a no-op and the
    result still arrives."""
    session = TridiagSession(SolverConfig(m=10, max_batch=1))
    try:
        session._engine._executor = WrappingExecutor(
            session._engine._executor, delay_s=0.2
        )
        dl, d, du, b = _sys(60, 0)
        fut = session.submit(SolveRequest(0, dl, d, du, b))
        # wait until the batch left the queue (in flight) but isn't done
        deadline = time.perf_counter() + 5.0
        while session.stats["queue_depth"] > 0 and time.perf_counter() < deadline:
            time.sleep(0.005)
        if not fut.done():
            assert fut.cancel() is False
        assert _rel_err(fut.result(timeout=10.0), thomas_numpy(dl, d, du, b)) < 1e-11
    finally:
        session.close()


# -------------------------------------------------------- stats + pending ---
def test_stats_is_a_snapshot_not_the_live_dict():
    """session.stats must be safe to iterate while the worker dispatches:
    it returns an isolated copy (mutating it changes nothing), taken under
    the lock, with the cache stats folded in."""
    with TridiagSession(SolverConfig(m=10, max_batch=2)) as session:
        f0 = session.submit(SolveRequest(0, *_sys(60, 0)))
        f1 = session.submit(SolveRequest(1, *_sys(60, 1)))
        f0.result(timeout=10.0), f1.result(timeout=10.0)
        snap = session.stats
        assert snap is not session._engine.stats
        assert snap["per_batch"] is not session._engine.stats["per_batch"]
        n_batches = snap["batches"]
        snap["batches"] = 999
        snap["per_batch"].append({"forged": True})
        snap["per_batch"][0]["systems"] = -1
        fresh = session.stats
        assert fresh["batches"] == n_batches
        assert all("forged" not in pb for pb in fresh["per_batch"])
        assert fresh["per_batch"][0]["systems"] == 2
        for cache_key in ("plan_cache", "executable_cache"):
            assert {"hits", "misses"} <= set(fresh[cache_key])


def test_stats_reads_race_free_under_traffic():
    """Reader thread iterating session.stats concurrently with dispatches:
    no RuntimeError('dict changed size during iteration') / torn reads."""
    errors = []
    stop = threading.Event()
    session = TridiagSession(SolverConfig(m=10, max_batch=1))
    try:
        def reader():
            while not stop.is_set():
                try:
                    snap = session.stats
                    for pb in snap["per_batch"]:
                        sum(v for v in pb.values() if isinstance(v, (int, float)))
                except Exception as e:  # pragma: no cover - the failure mode
                    errors.append(e)
                    return

        t = threading.Thread(target=reader)
        t.start()
        futs = [session.submit(SolveRequest(i, *_sys(60, i % 5))) for i in range(40)]
        for f in futs:
            f.result(timeout=30.0)
        stop.set()
        t.join(timeout=10.0)
        assert errors == []
    finally:
        stop.set()
        session.close()


def test_pending_counts_inflight_batch():
    """pending() counts unresolved futures — including a batch that has been
    TAKEN from the engine queue but not resolved yet (the engine queue
    length alone would report 0 and lie)."""
    session = TridiagSession(SolverConfig(m=10, max_batch=1))
    try:
        session._engine._executor = WrappingExecutor(
            session._engine._executor, delay_s=0.25
        )
        fut = session.submit(SolveRequest(0, *_sys(60, 0)))
        # wait for the worker to take the batch: queue empties, future open
        deadline = time.perf_counter() + 5.0
        while session.stats["queue_depth"] > 0 and time.perf_counter() < deadline:
            time.sleep(0.005)
        if not fut.done():  # in flight
            assert session.pending() == 1
        fut.result(timeout=10.0)
        assert session.pending() == 0
    finally:
        session.close()


# ----------------------------------------------- predicted-latency admission --
def _planted_model(pred_ms):
    """A latency model predicting a constant ``pred_ms`` for every batch."""
    from repro.api import LatencyModel

    return LatencyModel(coef=(float(pred_ms), 0.0, 0.0), samples=1)


def test_predicted_shed_fires_before_dispatch():
    """A queued request whose predicted completion blows its own deadline is
    shed with PredictedTimeoutError BEFORE any dispatch touches it — the
    executor must never see its batch."""
    session = TridiagSession(
        SolverConfig(m=10, max_batch=64, max_wait_ms=50.0, max_predicted_ms=50.0)
    )
    try:
        counting = WrappingExecutor(session._engine._executor)
        session._engine._executor = counting
        # Every solve is predicted to take 1000 ms; a 100 ms deadline is
        # structurally unmeetable.
        session._engine.set_latency_model(_planted_model(1000.0))
        fut = session.submit(SolveRequest(0, *_sys(60, 0), timeout_ms=100.0))
        err = fut.exception(timeout=10.0)
        assert isinstance(err, api_mod.PredictedTimeoutError)
        assert isinstance(err, RequestTimedOutError)  # deadline-aware callers
        assert counting.calls == 0  # shed pre-dispatch, never executed
        st = session.stats
        assert st["shed_predicted"] == 1
        assert st["timed_out"] == 1
        assert st["batches"] == 0
        # A deadline-less request on the same session still serves normally.
        dl, d, du, b = _sys(60, 1)
        f2 = session.submit(SolveRequest(1, dl, d, du, b))
        assert _rel_err(f2.result(timeout=10.0), thomas_numpy(dl, d, du, b)) < 1e-11
        assert counting.calls == 1
    finally:
        session.close()


def test_predicted_shed_needs_the_budget_knob():
    """Without max_predicted_ms the model is advisory only: predictions are
    recorded, nothing is shed."""
    session = TridiagSession(SolverConfig(m=10, max_batch=1))
    try:
        session._engine.set_latency_model(_planted_model(1000.0))
        dl, d, du, b = _sys(60, 0)
        fut = session.submit(SolveRequest(0, dl, d, du, b, timeout_ms=60_000.0))
        assert _rel_err(fut.result(timeout=10.0), thomas_numpy(dl, d, du, b)) < 1e-11
        assert session.stats["shed_predicted"] == 0
    finally:
        session.close()


def test_budget_packs_batches_and_defers_the_rest():
    """Engine-level: with predicted latency linear in effective size and a
    50 ms budget, a 6-deep queue of 60-element systems (predicted 20 ms
    each... per-batch = eff_size/3 ms) packs 2 per dispatch — admission
    order preserved, everything eventually served."""
    from repro.api import LatencyModel

    done, failed = {}, {}
    eng = SolveEngine(
        m=10,
        admission=api_mod.AdmissionPolicy(max_batch=64, max_wait_ms=0.0),
        max_predicted_ms=50.0,
        on_result=lambda rid, x: done.__setitem__(rid, x),
        on_error=lambda rid, e: failed.__setitem__(rid, e),
    )
    # predict eff/3 ms: one 60-system -> 20ms, two -> 40ms, three -> 60ms.
    eng.set_latency_model(LatencyModel(coef=(0.0, 1.0 / 3.0, 0.0), samples=1))
    systems = {rid: _sys(60, rid) for rid in range(6)}
    for rid, s in systems.items():
        eng.submit(SolveRequest(rid, *s))
    while eng.pending():
        eng.poll()
    assert failed == {}
    assert sorted(done) == list(range(6))
    for rid, (dl, d, du, b) in systems.items():
        assert _rel_err(done[rid], thomas_numpy(dl, d, du, b)) < 1e-11
    st = eng.stats_snapshot()
    assert [pb["systems"] for pb in st["per_batch"]] == [2, 2, 2]
    # Packing defers, it never sheds: every request was served.
    assert st["shed_predicted"] == 0 and st["timed_out"] == 0


def test_solo_over_budget_request_still_dispatches():
    """_pack_by_budget must always keep >= 1 request, or an over-budget
    request would starve the queue forever."""
    from repro.api import LatencyModel

    done = {}
    eng = SolveEngine(
        m=10,
        admission=api_mod.AdmissionPolicy(max_batch=8, max_wait_ms=0.0),
        max_predicted_ms=1.0,  # everything is over budget
        on_result=lambda rid, x: done.__setitem__(rid, x),
        on_error=lambda rid, e: (_ for _ in ()).throw(e),
    )
    eng.set_latency_model(LatencyModel(coef=(100.0, 0.0, 0.0), samples=1))
    eng.submit(SolveRequest(0, *_sys(60, 0)))
    eng.submit(SolveRequest(1, *_sys(60, 1)))
    while eng.pending():
        eng.poll()
    assert sorted(done) == [0, 1]
    # Each rode alone: the budget trimmed every batch to the floor of one.
    assert [pb["systems"] for pb in eng.stats_snapshot()["per_batch"]] == [1, 1]


def test_dispatch_records_predicted_and_residual():
    """With a model active and telemetry on, every observation carries the
    pre-dispatch prediction, so predicted-vs-actual residuals are
    observable."""
    session = TridiagSession(
        SolverConfig(m=10, max_batch=2, max_wait_ms=5.0, max_predicted_ms=500.0)
    )
    try:
        session._engine.set_latency_model(_planted_model(7.5))
        futs = [
            session.submit(SolveRequest(rid, *_sys(60, rid))) for rid in (0, 1)
        ]
        for f in futs:
            f.result(timeout=10.0)
        snap = session.telemetry.snapshot()
        assert len(snap) >= 1
        for o in snap:
            assert o.predicted_ms == 7.5
            assert o.residual_ms == pytest.approx(o.latency_ms - 7.5)
    finally:
        session.close()
