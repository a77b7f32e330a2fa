"""Tests for the one front door: SolverConfig validation, TridiagSession's
four verbs (fp64+fp32 parity with the Thomas oracle on both backends from a
single shared config), the async SolveFuture path (deadline admission fires
via the worker thread — no poll() anywhere), and session lifecycle."""

import math
import threading
import time

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.api import (  # noqa: E402
    FixedChunkPolicy,
    SolveRequest,
    SolverConfig,
    TridiagSession,
)
from repro.core.tridiag.reference import (  # noqa: E402
    make_diag_dominant_system,
    thomas_numpy,
)

TOL = {np.float64: 1e-11, np.float32: 2e-4}


def _rel_err(x, ref):
    x = np.asarray(x, np.float64)
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


def _mk_systems(sizes, dtype=np.float64, seed0=0):
    return [
        make_diag_dominant_system(n, seed=seed0 + i, dtype=dtype)[:4]
        for i, n in enumerate(sizes)
    ]


# ------------------------------------------------------------------- config --
def test_config_defaults_validate():
    cfg = SolverConfig()
    assert cfg.validate() is cfg
    assert cfg.backend == "auto"
    assert cfg.m == 10
    assert math.isinf(cfg.max_wait_ms)


@pytest.mark.parametrize("bad,msg", [
    (dict(m=1), "m="),
    (dict(m=0), "m="),
    (dict(dtype=np.int32), "dtype"),
    (dict(dtype="not-a-dtype"), "dtype"),
    (dict(backend="cuda-streams"), "unknown stage backend"),
    (dict(num_chunks=0), "num_chunks"),
    (dict(policy=FixedChunkPolicy(2), num_chunks=4), "not both"),
    (dict(max_batch=0), "max_batch"),
    (dict(max_wait_ms=-1.0), "max_wait_ms"),
    (dict(plan_cache_capacity=-1), "plan_cache_capacity"),
])
def test_config_validate_actionable_errors(bad, msg):
    with pytest.raises((ValueError, TypeError), match=msg):
        SolverConfig(**bad).validate()


def test_config_validate_rejects_non_policy():
    with pytest.raises(TypeError, match="ChunkPolicy"):
        SolverConfig(policy=lambda sizes, m: 4).validate()


def test_config_is_frozen_and_replaceable():
    cfg = SolverConfig(m=10, num_chunks=2)
    with pytest.raises(Exception):
        cfg.m = 5
    cfg2 = cfg.replace(num_chunks=8)
    assert cfg.num_chunks == 2 and cfg2.num_chunks == 8
    assert cfg2.m == cfg.m


def test_session_validates_config_at_construction():
    with pytest.raises(ValueError, match="unknown stage backend"):
        TridiagSession(SolverConfig(backend="nope"))


def test_auto_backend_resolves_by_host(monkeypatch):
    """Satellite: backend="auto" resolves to Pallas on TPU hosts and the
    reference stages elsewhere, and is the config default."""
    from repro.core.tridiag import plan as plan_mod

    assert SolverConfig().backend == "auto"
    # This container is not a TPU host.
    assert plan_mod.resolve_backend("auto") == plan_mod.ReferenceBackend()
    assert TridiagSession(SolverConfig()).backend == plan_mod.ReferenceBackend()
    monkeypatch.setattr(plan_mod.jax, "default_backend", lambda: "tpu")
    assert plan_mod.resolve_backend("auto") == plan_mod.PallasBackend()
    assert "auto" in plan_mod.BACKENDS


# ----------------------------------------------- four verbs, shared config ---
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_all_four_verbs_match_thomas_from_one_config(backend, dtype):
    """Acceptance: one shared SolverConfig; solve / solve_batched /
    solve_many / submit all match the fp64 Thomas oracle on both backends."""
    cfg = SolverConfig(m=10, dtype=dtype, backend=backend, num_chunks=3,
                       max_batch=4)
    tol = TOL[dtype]
    with TridiagSession(cfg) as session:
        # solve: one system (fp64 inputs; the config's dtype casts them)
        dl, d, du, b, _ = make_diag_dominant_system(250, seed=0)
        ref = thomas_numpy(dl, d, du, b)
        x = session.solve(dl, d, du, b)
        assert np.asarray(x).dtype == np.dtype(dtype)
        assert _rel_err(x, ref) < tol

        # solve_batched: (B, n)
        DL, D, DU, B, _ = make_diag_dominant_system(120, seed=1, batch=(3,))
        xb = session.solve_batched(DL, D, DU, B)
        assert xb.shape == (3, 120)
        for i in range(3):
            assert _rel_err(xb[i], thomas_numpy(DL[i], D[i], DU[i], B[i])) < tol

        # solve_many: ragged mix
        systems = _mk_systems((60, 240, 120), seed0=2)
        xs = session.solve_many(systems)
        for xi, s in zip(xs, systems):
            assert _rel_err(xi, thomas_numpy(*s)) < tol

        # submit: async, resolved on close-drain at the latest
        futs = {
            rid: session.submit(SolveRequest(rid, *s))
            for rid, s in enumerate(_mk_systems((60, 120, 60, 240), seed0=9))
        }
        for rid, s in enumerate(_mk_systems((60, 120, 60, 240), seed0=9)):
            assert _rel_err(futs[rid].result(timeout=30.0), thomas_numpy(*s)) < tol


def test_solve_batched_rejects_1d_operands():
    dl, d, du, b, _ = make_diag_dominant_system(60, seed=0)
    with pytest.raises(ValueError, match="solve_batched takes"):
        TridiagSession(SolverConfig()).solve_batched(dl, d, du, b)


def test_policy_config_prices_each_dispatch():
    cfg = SolverConfig(m=10, policy=FixedChunkPolicy(5))
    session = TridiagSession(cfg)
    assert session.plan_for(600).num_chunks == 5
    dl, d, du, b, _ = make_diag_dominant_system(600, seed=6)
    x = session.solve(dl, d, du, b)
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < TOL[np.float64]
    assert session.plan_for(600).num_chunks == 5


# --------------------------------------------------------- async / futures ---
def test_submit_resolves_within_deadline_without_poll():
    """Acceptance: with real threads and a short deadline, the future
    resolves on its own — nobody calls poll(), flush() or close()."""
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=7)
    ref = thomas_numpy(dl, d, du, b)
    cfg = SolverConfig(m=10, max_batch=64, max_wait_ms=30.0)
    with TridiagSession(cfg) as session:
        session.solve(dl, d, du, b)  # warm the jit cache for this shape
        t0 = time.perf_counter()
        fut = session.submit(SolveRequest(0, dl, d, du, b))
        x = fut.result(timeout=10.0)  # blocks; no poll anywhere
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        assert _rel_err(x, ref) < 1e-11
        # The batch really waited for the deadline (it was alone in the
        # queue, far below max_batch), and resolution came promptly after.
        pb = session.stats["per_batch"][-1]
        assert pb["max_wait_ms"] >= 30.0
        assert elapsed_ms >= 30.0
        assert elapsed_ms < 5_000.0


def test_submit_dispatches_at_max_batch_without_deadline():
    """An inf deadline still serves: the worker dispatches on occupancy."""
    systems = _mk_systems((60, 60), seed0=11)
    cfg = SolverConfig(m=10, max_batch=2)  # max_wait_ms=inf
    with TridiagSession(cfg) as session:
        f0 = session.submit(SolveRequest(0, *systems[0]))
        f1 = session.submit(SolveRequest(1, *systems[1]))
        for f, s in zip((f0, f1), systems):
            assert _rel_err(f.result(timeout=10.0), thomas_numpy(*s)) < 1e-11
    assert session.stats["batches"] == 1  # one fused dispatch


def test_future_done_is_nonblocking_and_result_times_out():
    dl, d, du, b, _ = make_diag_dominant_system(60, seed=12)
    cfg = SolverConfig(m=10, max_batch=64)  # inf deadline: nothing dispatches
    session = TridiagSession(cfg)
    try:
        fut = session.submit(SolveRequest(0, dl, d, du, b))
        t0 = time.perf_counter()
        assert not fut.done()
        assert time.perf_counter() - t0 < 1.0  # done() didn't block
        with pytest.raises(TimeoutError, match="request 0"):
            fut.result(timeout=0.05)
    finally:
        session.close()
    assert fut.done()  # close() drained the queue


def test_submit_validates_diagonals_and_names_request():
    dl, d, du, b, _ = make_diag_dominant_system(60, seed=13)
    with TridiagSession(SolverConfig(m=10)) as session:
        with pytest.raises(ValueError, match="request 5"):
            session.submit(SolveRequest(5, dl[:-1], d, du, b))
        assert session.pending() == 0  # the bad request never enqueued


def test_duplicate_inflight_rid_is_rejected():
    s0, s1 = _mk_systems((60, 60), seed0=14)
    with TridiagSession(SolverConfig(m=10, max_batch=64)) as session:
        session.submit(SolveRequest(3, *s0))
        with pytest.raises(ValueError, match="already in flight"):
            session.submit(SolveRequest(3, *s1))


def test_concurrent_submitters_all_resolve():
    """Many threads submit into one session; every future resolves correctly
    (the plan/stage caches are hammered from the worker + submitters)."""
    cfg = SolverConfig(m=10, max_batch=8, max_wait_ms=20.0)
    systems = _mk_systems((60, 120, 240, 60, 120, 240, 60, 120), seed0=20)
    refs = [thomas_numpy(*s) for s in systems]
    futs = [None] * len(systems)
    with TridiagSession(cfg) as session:
        def submit_one(i):
            futs[i] = session.submit(SolveRequest(i, *systems[i]))

        threads = [
            threading.Thread(target=submit_one, args=(i,))
            for i in range(len(systems))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for fut, ref in zip(futs, refs):
            assert _rel_err(fut.result(timeout=30.0), ref) < 1e-11


# ---------------------------------------------------------------- lifecycle --
def test_close_drains_outstanding_futures():
    systems = _mk_systems((60, 120, 60), seed0=30)
    session = TridiagSession(SolverConfig(m=10, max_batch=64))  # inf deadline
    futs = [session.submit(SolveRequest(i, *s)) for i, s in enumerate(systems)]
    assert not any(f.done() for f in futs)
    session.close()
    for f, s in zip(futs, systems):
        assert f.done()
        assert _rel_err(f.result(timeout=0), thomas_numpy(*s)) < 1e-11


def test_double_close_is_idempotent_and_submit_after_close_raises():
    session = TridiagSession(SolverConfig(m=10))
    session.close()
    session.close()  # no-op, no error — even without any submit
    dl, d, du, b, _ = make_diag_dominant_system(60, seed=31)
    with pytest.raises(RuntimeError, match="closed"):
        session.submit(SolveRequest(0, dl, d, du, b))
    # synchronous verbs keep working after close
    assert _rel_err(session.solve(dl, d, du, b), thomas_numpy(dl, d, du, b)) < 1e-11


def test_context_manager_closes():
    with TridiagSession(SolverConfig(m=10)) as session:
        pass
    with pytest.raises(RuntimeError):
        dl, d, du, b, _ = make_diag_dominant_system(60, seed=32)
        session.submit(SolveRequest(0, dl, d, du, b))
