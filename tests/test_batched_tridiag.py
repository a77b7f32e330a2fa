"""Correctness tests for the batched multi-SLAE subsystem: functional solve,
the session's chunked ``solve_batched``, batched-grid Pallas kernels, and the
serving engine — all against the per-system NumPy Thomas oracle."""

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

import jax.numpy as jnp  # noqa: E402

from repro.core.tridiag import (  # noqa: E402
    AdmissionPolicy,
    SolveEngine,
    SolverConfig,
    TridiagSession,
    fuse_systems,
    make_diag_dominant_system,
    solve_batched,
    split_systems,
    thomas_batched,
    thomas_numpy,
)
from repro.kernels.common import assert_allclose_by_dtype  # noqa: E402
from repro.kernels.partition_stage1.ops import (  # noqa: E402
    partition_stage1_pallas_batched,
)
from repro.kernels.partition_stage1.ref import stage1_ref  # noqa: E402
from repro.kernels.partition_stage3.ops import (  # noqa: E402
    partition_solve_pallas_batched,
)
from repro.serve.solve import (  # noqa: E402
    SolveRequest,
    make_batched_solve_step,
)

TOL = {np.float64: 1e-11, np.float32: 2e-4}


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


def _per_system_ref(dl, d, du, b):
    return np.stack([thomas_numpy(*(a[i] for a in (dl, d, du, b)))
                     for i in range(d.shape[0])])


# ------------------------------------------------------------- functional ----
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("bsz,n,m", [(1, 200, 10), (4, 120, 10), (9, 60, 3)])
def test_solve_batched_matches_per_system_thomas(bsz, n, m, dtype):
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=bsz + n, batch=(bsz,), dtype=dtype)
    x = np.asarray(solve_batched(dl, d, du, b, m=m))
    assert x.shape == (bsz, n)
    assert x.dtype == np.dtype(dtype)
    assert _rel_err(x, _per_system_ref(dl, d, du, b)) < TOL[dtype]


def test_thomas_batched_reference():
    dl, d, du, b, _ = make_diag_dominant_system(75, seed=2, batch=(6,))
    x = np.asarray(thomas_batched(dl, d, du, b))
    assert _rel_err(x, _per_system_ref(dl, d, du, b)) < 1e-12


def test_solve_batched_rejects_bad_shapes():
    dl, d, du, b, _ = make_diag_dominant_system(50, seed=0)
    with pytest.raises(ValueError):
        solve_batched(dl, d, du, b, m=10)  # 1-D, not (batch, n)
    dl, d, du, b, _ = make_diag_dominant_system(50, seed=0, batch=(2,))
    with pytest.raises(ValueError):
        solve_batched(dl, d, du, b, m=7)  # n not divisible by m


# ------------------------------------------------------------ batch fusion ----
def test_fuse_systems_decouples_exactly():
    """The fused (B·n,) solve equals the per-system solves even with junk in
    the (ignored-by-convention) boundary entries."""
    bsz, n = 5, 80
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=3, batch=(bsz,))
    dl[:, 0] = 123.0   # convention says these are ignored; fusion must zero
    du[:, -1] = -77.0  # them or systems would couple
    fused = fuse_systems(dl, d, du, b)
    assert all(a.shape == (bsz * n,) for a in fused)
    x = split_systems(thomas_numpy(*fused), bsz)
    ref = _per_system_ref(dl, d, du, b)
    assert _rel_err(x, ref) < 1e-12


# ---------------------------------------------------------- chunked solver ----
def _session(m, num_chunks, backend="reference"):
    return TridiagSession(SolverConfig(m=m, num_chunks=num_chunks, backend=backend))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("num_chunks", [1, 2, 3, 7, 32])
@pytest.mark.parametrize("bsz", [1, 4])
def test_batched_chunked_solver_matches_reference(bsz, num_chunks, backend):
    # n/m = 13 blocks per system: chunk counts 2, 3, 7, 32 do not divide the
    # fused block count, exercising the ragged chunk-bounds path.
    n, m = 130, 10
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=num_chunks, batch=(bsz,))
    session = _session(m, num_chunks, backend)
    x = session.solve_batched(dl, d, du, b)
    assert x.shape == (bsz, n)
    assert _rel_err(x, _per_system_ref(dl, d, du, b)) < 1e-11
    assert session.plan_for((n,) * bsz).num_chunks == min(num_chunks, bsz * n // m)


def test_batched_chunks_span_system_boundaries():
    """With more chunks than any single system has blocks, chunking only
    works because the fused block axis spans the whole batch."""
    bsz, n, m = 8, 30, 10  # 3 blocks/system, 24 fused blocks
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=9, batch=(bsz,))
    session = _session(m, 16)
    x = session.solve_batched(dl, d, du, b)
    assert session.plan_for((n,) * bsz).num_chunks == 16  # > 3 blocks/system
    assert _rel_err(x, _per_system_ref(dl, d, du, b)) < 1e-11


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_batched_chunked_solver_fp32(dtype, backend):
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=1, batch=(3,), dtype=dtype)
    x = _session(10, 4, backend).solve_batched(dl, d, du, b)
    assert _rel_err(x, _per_system_ref(dl, d, du, b)) < TOL[dtype]


# ----------------------------------------------------------- pallas kernels ----
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("bsz,p,m", [(1, 4, 10), (3, 100, 10), (5, 33, 5), (2, 129, 3)])
def test_stage1_batched_kernel_sweep(bsz, p, m, dtype):
    n = p * m
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=p + m, batch=(bsz,), dtype=dtype)
    args = tuple(map(jnp.asarray, (dl, d, du, b)))
    got = partition_stage1_pallas_batched(*args, m=m, block_p=128)
    want = stage1_ref(*args, m=m)  # partition_stage1 is batch-dim polymorphic
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert_allclose_by_dtype(g, w, dtype)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_partition_solve_pallas_batched_end_to_end(dtype):
    bsz, n, m = 4, 500, 10
    dl, d, du, b, x_true = make_diag_dominant_system(n, seed=42, batch=(bsz,), dtype=dtype)
    x = np.asarray(
        partition_solve_pallas_batched(*map(jnp.asarray, (dl, d, du, b)), m=m)
    )
    assert x.shape == (bsz, n)
    tol = 1e-8 if dtype == np.float64 else 2e-3
    assert np.max(np.abs(x - x_true)) < tol


# ------------------------------------------------------------------ serving ----
def test_batched_solve_step_builder():
    step = make_batched_solve_step(m=10)
    dl, d, du, b, _ = make_diag_dominant_system(100, seed=6, batch=(3,))
    x = np.asarray(step(dl, d, du, b))
    assert _rel_err(x, _per_system_ref(dl, d, du, b)) < 1e-11


def test_solve_service_batches_same_size_requests():
    svc = SolveEngine(m=10, admission=AdmissionPolicy(max_batch=4), default_chunks=2)
    refs = {}
    rid = 0
    for size, count in ((60, 6), (120, 3)):
        for j in range(count):
            dl, d, du, b, _ = make_diag_dominant_system(size, seed=rid)
            svc.submit(SolveRequest(rid, dl, d, du, b))
            refs[rid] = thomas_numpy(dl, d, du, b)
            rid += 1
    assert svc.pending() == 9
    out = svc.flush()
    assert svc.pending() == 0
    assert set(out) == set(refs)
    for r, x in out.items():
        assert _rel_err(x, refs[r]) < 1e-11
    # 6 size-60 requests at max_batch=4 -> 2 batches; 3 size-120 -> 1 batch.
    assert svc.stats["batches"] == 3
    assert svc.stats["systems"] == 9
    assert svc.systems_per_sec > 0


def test_solve_service_uses_heuristic_pick():
    from repro.core.autotune.heuristic import fit_batched_stream_heuristic
    from repro.core.streams import StreamSimulator

    sim = StreamSimulator(seed=1)
    h = fit_batched_stream_heuristic(
        sim.dataset(sizes=(10_000, 100_000, 1_000_000, 10_000_000),
                    batches=(1, 8, 64), reps=2)
    )
    svc = SolveEngine(heuristic=h, m=10)
    assert svc.pick_chunks(10_000, 1) == h.predict_optimum(10_000, 1)
    assert svc.pick_chunks(100_000, 64) == h.predict_optimum(100_000, 64)
    # a big batch of small systems must want more chunks than a single one
    assert svc.pick_chunks(100_000, 64) > svc.pick_chunks(100_000, 1)


def test_solve_service_rejects_indivisible_size():
    svc = SolveEngine(m=10)
    dl, d, du, b, _ = make_diag_dominant_system(55, seed=0)
    with pytest.raises(ValueError):
        svc.submit(SolveRequest(0, dl, d, du, b))
