"""Tests for the pluggable stage backend of the plan executor: Pallas-kernel
parity with the fp64/fp32 Thomas oracle on every planned path (single,
batched, ragged, serving), backend resolution and the (m, backend) stage
cache, and the (sizes, m, num_chunks) plan cache."""

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.core.tridiag import (  # noqa: E402
    BACKENDS,
    AdmissionPolicy,
    PallasBackend,
    PlanExecutor,
    ReferenceBackend,
    SolveEngine,
    SolverConfig,
    TridiagSession,
    build_plan,
    clear_plan_cache,
    jitted_stages,
    make_diag_dominant_system,
    plan_cache_stats,
    resolve_backend,
    thomas_numpy,
)
from repro.serve.solve import SolveRequest  # noqa: E402

TOL = {np.float64: 1e-11, np.float32: 2e-4}


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


def _session(num_chunks, backend="pallas"):
    return TridiagSession(SolverConfig(m=10, num_chunks=num_chunks, backend=backend))


def _mk_systems(sizes, dtype=np.float64, seed0=0):
    return [
        make_diag_dominant_system(n, seed=seed0 + i, dtype=dtype)[:4]
        for i, n in enumerate(sizes)
    ]


# ----------------------------------------------------------- resolution ------
def test_resolve_backend_names_and_instances():
    assert resolve_backend(None) == ReferenceBackend()
    assert resolve_backend("reference") == ReferenceBackend()
    assert resolve_backend("pallas") == PallasBackend()
    be = PallasBackend(block_p=64)
    assert resolve_backend(be) is be
    with pytest.raises(ValueError):
        resolve_backend("cuda-streams")
    with pytest.raises(TypeError):
        resolve_backend(42)
    assert set(BACKENDS) >= {"reference", "pallas"}


def test_stage_cache_keyed_by_m_and_backend():
    ref = jitted_stages(10)
    assert jitted_stages(10, "reference") == ref  # None is the reference default
    pal = jitted_stages(10, "pallas")
    assert pal[0] is not ref[0] and pal[1] is not ref[1]
    # value-equal backend instances share the cache entry
    assert jitted_stages(10, PallasBackend()) == pal
    # a differently-configured backend gets its own stages
    assert jitted_stages(10, PallasBackend(block_p=64))[0] is not pal[0]


# -------------------------------------------------------------- parity -------
@pytest.mark.parametrize("num_chunks", [1, 3, 7])
def test_pallas_backend_single_matches_thomas(num_chunks):
    dl, d, du, b, _ = make_diag_dominant_system(400, seed=num_chunks)
    session = _session(num_chunks)
    x = session.solve(dl, d, du, b)
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-11
    assert session.plan_for(400).num_chunks == num_chunks
    assert session.stats["backend"]["name"] == "pallas"


def test_pallas_backend_batched_matches_per_system_thomas():
    dl, d, du, b, _ = make_diag_dominant_system(240, seed=2, batch=(4,))
    x = _session(5).solve_batched(dl, d, du, b)
    for i in range(4):
        assert _rel_err(x[i], thomas_numpy(dl[i], d[i], du[i], b[i])) < 1e-11


def test_pallas_backend_leading_batch_axis_uses_batched_grid():
    """(B, n) fused operands ride one plan through the batched-grid kernels."""
    dl, d, du, b, _ = make_diag_dominant_system(240, seed=4, batch=(3,))
    plan = build_plan(240, 10, num_chunks=4)
    x, _ = PlanExecutor(backend="pallas").execute(plan, dl, d, du, b)
    assert x.shape == (3, 240)
    for i in range(3):
        assert _rel_err(x[i], thomas_numpy(dl[i], d[i], du[i], b[i])) < 1e-11


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("num_chunks", [1, 4])
def test_pallas_backend_ragged_matches_per_system_thomas(dtype, num_chunks):
    """Acceptance: fused ragged plans execute on the Pallas kernels and match
    per-system Thomas in both precisions."""
    sizes = (60, 240, 120, 500)
    systems = _mk_systems(sizes, dtype=dtype)
    session = _session(num_chunks)
    xs = session.solve_many(systems)
    refs = [
        thomas_numpy(*[np.asarray(a, np.float64) for a in s]) for s in systems
    ]
    for x, ref in zip(xs, refs):
        assert _rel_err(np.asarray(x, np.float64), ref) < TOL[dtype]
    assert session.plan_for(sizes).num_chunks == min(num_chunks, sum(sizes) // 10)


def test_pallas_and_reference_backends_agree_exactly_on_layout():
    """Same plan, same operands: the two backends must agree to fp64
    round-off, chunk by chunk (not just against the oracle)."""
    systems = _mk_systems((200, 1000, 300))
    xs_ref = _session(6, "reference").solve_many(systems)
    xs_pal = _session(6).solve_many(systems)
    for a, b in zip(xs_ref, xs_pal):
        np.testing.assert_allclose(a, b, rtol=1e-12, atol=1e-12)


def test_serving_dispatches_on_pallas_backend():
    refs = {}
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=8), backend="pallas")
    for rid, size in enumerate((60, 240, 60, 120)):
        dl, d, du, b, _ = make_diag_dominant_system(size, seed=rid)
        svc.submit(SolveRequest(rid, dl, d, du, b))
        refs[rid] = thomas_numpy(dl, d, du, b)
    out = svc.flush()
    assert set(out) == set(refs)
    for rid, x in out.items():
        assert _rel_err(x, refs[rid]) < 1e-11
    assert svc.stats["batches"] == 1  # the mixed sizes fused into one dispatch


# ------------------------------------------------------------ plan cache -----
def test_plan_cache_hit_returns_same_plan_object():
    clear_plan_cache()
    p1 = build_plan((60, 240), 10, num_chunks=3)
    stats = plan_cache_stats()
    assert (stats["hits"], stats["misses"]) == (0, 1)
    p2 = build_plan((60, 240), 10, num_chunks=3)
    assert p2 is p1
    stats = plan_cache_stats()
    assert (stats["hits"], stats["misses"]) == (1, 1)
    # any signature component changes -> miss
    assert build_plan((60, 240), 10, num_chunks=4) is not p1
    assert build_plan((240, 60), 10, num_chunks=3) is not p1
    assert build_plan((60, 240), 5, num_chunks=3) is not p1
    assert plan_cache_stats()["misses"] == 4


def test_plan_cache_keyed_by_resolved_chunk_count():
    """A policy pick and an explicit num_chunks with the same resolved count
    share one cache entry (the cache keys the *resolved* signature)."""
    from repro.core.tridiag import FixedChunkPolicy

    clear_plan_cache()
    p1 = build_plan((100, 100), 10, policy=FixedChunkPolicy(4))
    p2 = build_plan((100, 100), 10, num_chunks=4)
    assert p2 is p1
    # clamped counts collapse onto the same entry too: 99 chunks > 20 blocks
    p3 = build_plan((100, 100), 10, num_chunks=99)
    p4 = build_plan((100, 100), 10, num_chunks=20)
    assert p3 is p4


def test_clear_plan_cache_resets_counters():
    build_plan(100, 10)
    clear_plan_cache()
    assert plan_cache_stats() == {"hits": 0, "misses": 0, "size": 0}
