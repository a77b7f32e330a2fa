"""Correctness tests for ragged mixed-size batch fusion: exact decoupling of
heterogeneous systems in one fused chunked solve, offset-table round-trips,
and effective-size pricing through the batched stream heuristic."""

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.core.tridiag import (  # noqa: E402
    HeuristicChunkPolicy,
    SolverConfig,
    TridiagSession,
    fuse_ragged,
    make_diag_dominant_system,
    split_ragged,
    thomas_numpy,
)

TOL = {np.float64: 1e-11, np.float32: 2e-4}


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


def _mk_systems(sizes, dtype=np.float64, seed0=0):
    return [
        make_diag_dominant_system(n, seed=seed0 + i, dtype=dtype)[:4]
        for i, n in enumerate(sizes)
    ]


# ------------------------------------------------------------------ fusion ---
def test_fuse_ragged_zeroes_boundary_couplings():
    """Junk in the (ignored-by-convention) boundary entries must not couple
    neighbouring systems in the fused solve."""
    systems = _mk_systems((60, 240, 120))
    for dl, d, du, b in systems:
        dl[0] = 123.0
        du[-1] = -77.0
    dl, d, du, b, sizes = fuse_ragged(systems)
    assert sizes == (60, 240, 120)
    assert all(a.shape == (420,) for a in (dl, d, du, b))
    xs = split_ragged(thomas_numpy(dl, d, du, b), sizes)
    for (sdl, sd, sdu, sb), x in zip(systems, xs):
        assert _rel_err(x, thomas_numpy(sdl, sd, sdu, sb)) < 1e-12


def test_split_ragged_round_trip_and_validation():
    sizes = (30, 50, 20)
    x = np.arange(100, dtype=np.float64)
    parts = split_ragged(x, sizes)
    assert [p.shape[-1] for p in parts] == list(sizes)
    np.testing.assert_array_equal(np.concatenate(parts), x)
    with pytest.raises(ValueError):
        split_ragged(x, (30, 50))  # sizes don't sum to len(x)


def test_fuse_ragged_rejects_bad_input():
    with pytest.raises(ValueError):
        fuse_ragged([])
    dl, d, du, b, _ = make_diag_dominant_system(60, seed=0, batch=(2,))
    with pytest.raises(ValueError):
        fuse_ragged([(dl, d, du, b)])  # 2-D operands


@pytest.mark.parametrize("diag", ["dl", "du", "b"])
def test_fuse_ragged_rejects_mismatched_diagonal_lengths(diag):
    """Regression: one malformed request (a short/long diagonal) used to fuse
    silently, shifting every subsequent system's rows and corrupting all their
    solutions. It must be rejected, naming the offending system."""
    good, bad, tail = _mk_systems((60, 120, 60))
    idx = {"dl": 0, "du": 2, "b": 3}[diag]
    bad = list(bad)
    bad[idx] = bad[idx][:-1]  # one row short
    with pytest.raises(ValueError) as exc:
        fuse_ragged([good, tuple(bad), tail])
    assert "system 1" in str(exc.value)
    assert diag in str(exc.value)


def test_fuse_ragged_promotes_mixed_dtypes():
    s32 = _mk_systems((60,), dtype=np.float32)[0]
    s64 = _mk_systems((120,), dtype=np.float64, seed0=1)[0]
    dl, d, du, b, sizes = fuse_ragged([s32, s64])
    assert d.dtype == np.float64
    assert sizes == (60, 120)


# ------------------------------------------------------------- fused solve ---
def _session(backend="reference", **cfg):
    return TridiagSession(SolverConfig(m=10, backend=backend, **cfg))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("num_chunks", [1, 2, 4, 32])
def test_ragged_solve_matches_per_system_thomas(num_chunks, backend):
    """The acceptance mix {200, 1000, 5000} in one plan, fp64 oracle."""
    sizes = (200, 1000, 5000)
    systems = _mk_systems(sizes, seed0=num_chunks)
    xs = _session(backend, num_chunks=num_chunks).solve_many(systems)
    assert [x.shape[-1] for x in xs] == list(sizes)
    for (dl, d, du, b), x in zip(systems, xs):
        assert _rel_err(x, thomas_numpy(dl, d, du, b)) < TOL[np.float64]


def test_ragged_chunks_span_system_boundaries():
    """With more chunks than any single system has blocks, chunking only works
    because the fused block axis spans the whole heterogeneous batch."""
    sizes = (30, 60, 30, 90, 30)  # 3..9 blocks each, 24 fused blocks
    systems = _mk_systems(sizes, seed0=9)
    session = _session(num_chunks=16)
    xs = session.solve_many(systems)
    # > 9 = the largest per-system block count
    assert session.plan_for(sizes).num_chunks == 16
    for (dl, d, du, b), x in zip(systems, xs):
        assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-11


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_ragged_solve_fp32(dtype, backend):
    systems = _mk_systems((100, 300, 200), dtype=dtype, seed0=3)
    xs = _session(backend, num_chunks=4).solve_many(systems)
    for (dl, d, du, b), x in zip(systems, xs):
        assert _rel_err(x, thomas_numpy(dl, d, du, b)) < TOL[dtype]


def test_ragged_single_system_degenerates_to_chunked():
    (sys0,) = _mk_systems((400,), seed0=5)
    session = _session(num_chunks=3)
    xs = session.solve_many([sys0])
    ref = session.solve(*sys0)
    np.testing.assert_allclose(xs[0], ref, rtol=0, atol=0)


def test_ragged_rejects_indivisible_size():
    systems = _mk_systems((60, 55))
    with pytest.raises(ValueError):
        _session().solve_many(systems)


def test_ragged_solver_rejects_num_chunks_with_policy():
    from repro.core.tridiag import FixedChunkPolicy

    with pytest.raises(ValueError):
        _session(num_chunks=8, policy=FixedChunkPolicy(2))


def test_ragged_campaign_keeps_equal_total_mixes_apart():
    """Two mixes with the same Σ nᵢ must both contribute Eq.-4 sum rows."""
    from repro.core.streams.measure import measure_ragged_dataset

    data = measure_ragged_dataset([(60, 240), (120, 180)], candidates=(1, 2), reps=1)
    sizes, sums = data.per_size_sum()
    assert len(sizes) == 2  # one sum row per mix, not deduped on the total
    assert all(s == 300 for s in sizes)


def test_fused_stage_times_generalises_batched():
    from repro.core.streams import StreamSimulator, batched_stage_times, fused_stage_times

    sim = StreamSimulator()
    st = sim.components(100_000)
    fused, scaled = fused_stage_times([st] * 8), batched_stage_times(st, 8)
    for f in type(st).__dataclass_fields__:
        assert getattr(fused, f) == pytest.approx(getattr(scaled, f), rel=1e-12)
    mixed = fused_stage_times([sim.components(n) for n in (40_000, 400_000)])
    assert mixed.t1_comp == pytest.approx(
        sim.components(40_000).t1_comp + sim.components(400_000).t1_comp
    )
    with pytest.raises(ValueError):
        fused_stage_times([])


# ------------------------------------------------- effective-size pricing ----
@pytest.fixture(scope="module")
def batched_heuristic():
    from repro.core.autotune.heuristic import fit_batched_stream_heuristic
    from repro.core.streams import StreamSimulator

    sim = StreamSimulator(seed=1)
    return fit_batched_stream_heuristic(
        sim.dataset(sizes=(10_000, 100_000, 1_000_000, 10_000_000),
                    batches=(1, 8, 64), reps=2)
    )


def test_predict_optimum_ragged_equals_effective_size_pick(batched_heuristic):
    h = batched_heuristic
    sizes = (2_000_000, 2_000_000, 4_000_000)
    assert h.predict_optimum_ragged(sizes) == h.base.predict_optimum(8_000_000)
    # equal-sizes special case agrees with the (size, batch) feature
    assert h.predict_optimum_ragged((100_000,) * 64 ) == h.predict_optimum(100_000, 64)


def test_ragged_solver_uses_policy_pick(batched_heuristic):
    h = batched_heuristic
    sizes = (200, 1000, 5000)
    plan = _session(policy=HeuristicChunkPolicy(h)).plan_for(sizes)
    assert plan.num_chunks == min(
        h.predict_optimum_ragged(sizes), sum(sizes) // 10
    )
    # a big ragged batch must want more chunks than a small one
    big = (2_000_000, 4_000_000, 2_000_000)
    assert h.predict_optimum_ragged(big) > h.predict_optimum_ragged(sizes)
