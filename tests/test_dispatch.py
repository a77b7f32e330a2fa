"""Fused single-dispatch execution path: parity with the staged per-phase
instrument on every planned path (single, batched, ragged, served) ×
fp64/fp32 × both backends, the fused-executable LRU (hit/miss/eviction
stats, capacity, clear), donation semantics, and a two-thread session hammer
over the new cache."""

import threading

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import (  # noqa: E402
    SolveEngine,
    SolveRequest,
    SolverConfig,
    TridiagSession,
)
from repro.core.streams.measure import staged_timing  # noqa: E402
from repro.core.tridiag.batched import fuse_systems, split_systems  # noqa: E402
from repro.core.tridiag.layout import (  # noqa: E402
    AUTO_INTERLEAVE_MIN_BATCH,
    resolve_layout,
)
from repro.core.tridiag.plan import (  # noqa: E402
    FusedExecutor,
    build_plan,
    clear_executable_cache,
    executable_cache_stats,
    set_executable_cache_capacity,
)
from repro.core.tridiag.ragged import fuse_ragged, split_ragged  # noqa: E402
from repro.core.tridiag.reference import (  # noqa: E402
    make_diag_dominant_system,
    thomas_numpy,
)

# The staged path solves Stage 2 in fp64 on the host regardless of operand
# dtype; the fused path keeps the reduced solve on device in the operands'
# precision, so fp32 gets the plain single-precision tolerance.
TOL = {np.float64: 1e-11, np.float32: 2e-4}


def _rel_err(x, ref):
    return np.max(np.abs(np.asarray(x, np.float64) - ref)) / (
        np.max(np.abs(ref)) + 1e-30
    )


def _mk_systems(sizes, dtype=np.float64, seed0=0):
    return [
        make_diag_dominant_system(n, seed=seed0 + i, dtype=dtype)[:4]
        for i, n in enumerate(sizes)
    ]


@pytest.fixture(autouse=True)
def _fresh_executable_cache():
    """Isolate the process-wide executable LRU per test (stats + capacity)."""
    clear_executable_cache()
    yield
    set_executable_cache_capacity(128)
    clear_executable_cache()


# ------------------------------------------------------------------ parity ---
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_fused_matches_staged_on_all_paths(backend, dtype):
    """The session and the staged instrument on the same plan:
    identical-within-tolerance solutions on the single, batched and ragged
    paths."""
    fused = TridiagSession(
        SolverConfig(m=10, num_chunks=3, backend=backend, dtype=dtype)
    )
    tol = TOL[dtype]

    def staged(sizes, *ops):
        return staged_timing(sizes, *ops, m=10, num_chunks=3, backend=backend)[0]

    dl, d, du, b, _ = make_diag_dominant_system(300, seed=0, dtype=dtype)
    ref = thomas_numpy(dl, d, du, b)
    xs, xf = staged(300, dl, d, du, b), fused.solve(dl, d, du, b)
    assert _rel_err(xs, ref) < tol and _rel_err(xf, ref) < tol
    np.testing.assert_allclose(xf, xs, rtol=tol, atol=tol)

    DL, D, DU, B, _ = make_diag_dominant_system(120, seed=1, batch=(3,), dtype=dtype)
    xbs = split_systems(staged((120,) * 3, *fuse_systems(DL, D, DU, B)), 3)
    xbf = fused.solve_batched(DL, D, DU, B)
    for i in range(3):
        ref = thomas_numpy(DL[i], D[i], DU[i], B[i])
        assert _rel_err(xbf[i], ref) < tol
    np.testing.assert_allclose(xbf, xbs, rtol=tol, atol=tol)

    systems = _mk_systems((60, 240, 120), dtype=dtype, seed0=2)
    *ops, sizes = fuse_ragged(systems)
    for xi_s, xi_f, s in zip(
        split_ragged(staged(sizes, *ops), sizes), fused.solve_many(systems), systems
    ):
        assert _rel_err(xi_f, thomas_numpy(*s)) < tol
        np.testing.assert_allclose(xi_f, xi_s, rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_fused_serving_path_matches_oracle(backend):
    """submit() serves each batch as one fused dispatch; every future's
    solution sits on the fp64 oracle."""
    cfg = SolverConfig(m=10, num_chunks=2, backend=backend, max_wait_ms=5.0)
    assert cfg.dispatch == "auto"
    systems = _mk_systems((60, 120, 60, 240), seed0=7)
    with TridiagSession(cfg) as session:
        futs = [
            session.submit(SolveRequest(rid, *s)) for rid, s in enumerate(systems)
        ]
        for fut, s in zip(futs, systems):
            assert _rel_err(fut.result(timeout=30.0), thomas_numpy(*s)) < 1e-11
    assert session.stats["batches"] >= 1


@pytest.mark.parametrize("mode", ["staged", "fused", "bogus"])
def test_dispatch_validation_and_auto_timed_rule(mode):
    """``dispatch`` keeps one legal value, "auto"; every other value is
    refused with a pointer to the per-phase instrument, and the session
    serves on the fused executor alone."""
    assert SolverConfig(dispatch="auto").validate().dispatch == "auto"
    with pytest.raises(ValueError, match=f"dispatch='{mode}'.*staged_timing"):
        SolverConfig(dispatch=mode).validate()
    with pytest.raises(ValueError, match="staged_timing"):
        TridiagSession(SolverConfig(m=10, dispatch=mode))
    assert isinstance(SolveEngine(m=10)._executor, FusedExecutor)


# ---------------------------------------------------------------- donation ---
def test_fused_donation_consumes_device_arrays_numpy_safe():
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=4)
    plan = build_plan(200, 10, num_chunks=2)
    ex = FusedExecutor("reference")
    ref = thomas_numpy(dl, d, du, b)

    # numpy operands: copied to device per call, always safe to reuse.
    for _ in range(3):
        x, _ = ex.execute(plan, dl, d, du, b)
    assert _rel_err(x, ref) < 1e-11

    # device operands: donated to the executable — consumed by the solve.
    device_ops = [jnp.asarray(a) for a in (dl, d, du, b)]
    x, _ = ex.execute(plan, *device_ops)
    assert _rel_err(x, ref) < 1e-11
    with pytest.raises(RuntimeError):
        np.asarray(device_ops[0])  # trd: allow[TRD002] — asserts the deletion

    # donate=False keeps device operands alive (separate executable).
    keep = FusedExecutor("reference", donate=False)
    device_ops = [jnp.asarray(a) for a in (dl, d, du, b)]
    x, _ = keep.execute(plan, *device_ops)
    assert _rel_err(x, ref) < 1e-11
    np.testing.assert_array_equal(np.asarray(device_ops[1]), d)


# ----------------------------------------------------------- executable LRU --
def test_executable_cache_hits_misses_evictions():
    ex = FusedExecutor("reference")
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=5)

    plan2 = build_plan(200, 10, num_chunks=2)
    ex.execute(plan2, dl, d, du, b)
    stats = executable_cache_stats()
    assert (stats["misses"], stats["hits"], stats["size"]) == (1, 0, 1)

    ex.execute(plan2, dl, d, du, b)
    ex.execute(plan2, dl, d, du, b)
    assert executable_cache_stats()["hits"] == 2

    # A different chunking is a different plan signature -> new executable;
    # a different dtype re-keys too.
    plan3 = build_plan(200, 10, num_chunks=3)
    ex.execute(plan3, dl, d, du, b)
    ops32 = [np.asarray(a, np.float32) for a in (dl, d, du, b)]
    ex.execute(plan2, *ops32)
    stats = executable_cache_stats()
    assert stats["misses"] == 3 and stats["size"] == 3

    # Shrinking the capacity evicts oldest-first and counts it.
    set_executable_cache_capacity(1)
    stats = executable_cache_stats()
    assert stats["size"] == 1 and stats["evictions"] == 2

    # Capacity 0 disables caching: solves still work, nothing is retained.
    set_executable_cache_capacity(0)
    ex.execute(plan2, dl, d, du, b)
    assert executable_cache_stats()["size"] == 0

    with pytest.raises(ValueError):
        set_executable_cache_capacity(-1)

    clear_executable_cache()
    stats = executable_cache_stats()
    assert stats == {"hits": 0, "misses": 0, "evictions": 0, "size": 0}


def test_executable_cache_eviction_churn_stays_correct():
    """With a capacity smaller than the working set, every solve recompiles
    or evicts — results must stay on the oracle throughout."""
    set_executable_cache_capacity(2)
    ex = FusedExecutor("reference")
    cases = []
    for i, (n, k) in enumerate([(100, 1), (200, 2), (300, 3), (400, 4)]):
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=10 + i)
        cases.append((build_plan(n, 10, num_chunks=k), (dl, d, du, b)))
    for _ in range(3):
        for plan, ops in cases:
            x, _ = ex.execute(plan, *ops)
            assert _rel_err(x, thomas_numpy(*ops)) < 1e-11
    stats = executable_cache_stats()
    assert stats["size"] <= 2 and stats["evictions"] >= len(cases)


# ------------------------------------------------------------ layouts --------
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("layout", ["system-major", "interleaved"])
def test_layout_parity_on_all_paths(backend, layout):
    """Explicit layouts agree with the fp64 oracle on the single, batched
    and ragged paths, both dtypes."""
    for dtype in (np.float64, np.float32):
        session = TridiagSession(
            SolverConfig(m=10, num_chunks=2, backend=backend, dtype=dtype, layout=layout)
        )
        tol = TOL[dtype]

        dl, d, du, b, _ = make_diag_dominant_system(200, seed=11, dtype=dtype)
        ref = thomas_numpy(dl, d, du, b)
        assert _rel_err(session.solve(dl, d, du, b), ref) < tol

        DL, D, DU, B, _ = make_diag_dominant_system(
            120, seed=12, batch=(8,), dtype=dtype
        )
        xb = session.solve_batched(DL, D, DU, B)
        for i in range(8):
            assert _rel_err(xb[i], thomas_numpy(DL[i], D[i], DU[i], B[i])) < tol

        systems = _mk_systems((60, 240, 120), dtype=dtype, seed0=13)
        for xi, s in zip(session.solve_many(systems), systems):
            assert _rel_err(xi, thomas_numpy(*s)) < tol


def test_executable_cache_keys_layouts_separately():
    """The same plan compiled under two layouts must get two cache entries."""
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=14)
    plan = build_plan(200, 10, num_chunks=2)
    sm = FusedExecutor("reference", layout="system-major")
    il = FusedExecutor("reference", layout="interleaved")
    ref = thomas_numpy(dl, d, du, b)

    x, _ = sm.execute(plan, dl, d, du, b)
    assert _rel_err(x, ref) < 1e-11
    x, _ = il.execute(plan, dl, d, du, b)
    assert _rel_err(x, ref) < 1e-11
    stats = executable_cache_stats()
    assert (stats["misses"], stats["size"]) == (2, 2)

    sm.execute(plan, dl, d, du, b)
    il.execute(plan, dl, d, du, b)
    stats = executable_cache_stats()
    assert (stats["misses"], stats["hits"], stats["size"]) == (2, 2, 2)


def test_auto_layout_resolution_via_cache_key():
    """layout="auto" shares the wide executable with an explicit
    "interleaved" session at B >= the auto threshold, and the system-major
    executable below it."""
    bsz = AUTO_INTERLEAVE_MIN_BATCH
    dl, d, du, b, _ = make_diag_dominant_system(100, seed=15, batch=(bsz,))
    cfg = SolverConfig(m=10, num_chunks=1, backend="reference")
    auto = TridiagSession(cfg)
    assert auto.config.layout == "auto"
    auto.solve_batched(dl, d, du, b)
    assert executable_cache_stats()["misses"] == 1
    TridiagSession(cfg.replace(layout="interleaved")).solve_batched(dl, d, du, b)
    stats = executable_cache_stats()
    assert (stats["misses"], stats["hits"]) == (1, 1)

    dl2, d2, du2, b2, _ = make_diag_dominant_system(100, seed=16, batch=(4,))
    auto.solve_batched(dl2, d2, du2, b2)
    TridiagSession(cfg.replace(layout="system-major")).solve_batched(
        dl2, d2, du2, b2
    )
    stats = executable_cache_stats()
    assert (stats["misses"], stats["hits"]) == (2, 2)


def test_resolve_layout_rules_and_validation():
    m, n = 10, 100
    big = (n,) * AUTO_INTERLEAVE_MIN_BATCH
    # auto: wide flat batch -> interleaved; anything else system-major.
    assert resolve_layout("auto", big, m) == "interleaved"
    assert resolve_layout("auto", big[:-1], m) == "system-major"
    assert resolve_layout("auto", big, m, lead_ndim=1) == "system-major"
    # auto: ragged padding past the waste bound stays system-major.
    skewed = (40 * m,) + (m,) * (AUTO_INTERLEAVE_MIN_BATCH - 1)
    assert resolve_layout("auto", skewed, m) == "system-major"
    # explicit layouts pass through; interleaved rejects stacked operands.
    assert resolve_layout("system-major", big, m) == "system-major"
    assert resolve_layout("interleaved", (n,), m) == "interleaved"
    with pytest.raises(ValueError, match="interleaved"):
        resolve_layout("interleaved", big, m, lead_ndim=1)
    with pytest.raises(ValueError, match="layout"):
        resolve_layout("warp", big, m)

    with pytest.raises(ValueError, match="layout"):
        SolverConfig(layout="warp").validate()
    with pytest.raises(ValueError, match="layout"):
        SolveEngine(m=10, layout="warp")
    with pytest.raises(ValueError, match="layout"):
        FusedExecutor("reference", layout="warp")


def test_two_thread_session_hammer_over_executable_lru():
    """Two sessions solving concurrently (distinct plans, shared tiny LRU):
    the lock-protected cache must neither corrupt results nor deadlock."""
    set_executable_cache_capacity(2)
    cfg = SolverConfig(m=10)
    sizes = (100, 200, 300)
    problems = {
        (n, k): make_diag_dominant_system(n, seed=n + k)[:4]
        for n in sizes
        for k in (1, 2)
    }
    refs = {key: thomas_numpy(*ops) for key, ops in problems.items()}
    errors = []

    def worker(tid):
        session = TridiagSession(cfg.replace(num_chunks=1 + tid))
        try:
            for _ in range(10):
                for n in sizes:
                    ops = problems[(n, 1 + tid)]
                    x = session.solve(*ops)
                    if _rel_err(x, refs[(n, 1 + tid)]) > 1e-11:
                        errors.append((tid, n, "off oracle"))
        except Exception as e:  # pragma: no cover - failure path
            errors.append((tid, repr(e)))

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "hammer thread deadlocked"
    assert not errors, errors
    stats = executable_cache_stats()
    assert stats["size"] <= 2
    assert stats["hits"] + stats["misses"] >= 60
