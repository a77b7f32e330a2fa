"""Tests for the plan/execute layer: SolvePlan layout invariants, chunk
policies, the module-level jit cache, and the correctness of PlanExecutor,
the staged per-phase instrument."""

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.core.tridiag import (  # noqa: E402
    FixedChunkPolicy,
    HeuristicChunkPolicy,
    PlanExecutor,
    SolverConfig,
    TridiagSession,
    build_plan,
    effective_size,
    jitted_stages,
    make_diag_dominant_system,
    thomas_numpy,
)


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


# ------------------------------------------------------------------ layout ---
@pytest.mark.parametrize("sizes,m,k", [
    (400, 10, 3),            # single system, uneven split
    ((100, 200), 10, 4),     # same-m batch
    ((200, 1000, 5000), 10, 8),
    ((60,), 3, 32),          # k > num_blocks -> clamped
])
def test_plan_bounds_partition_fused_block_axis(sizes, m, k):
    plan = build_plan(sizes, m, num_chunks=k)
    assert plan.total_size == effective_size(sizes)
    assert plan.num_blocks * m == plan.total_size
    assert plan.num_chunks == min(k, plan.num_blocks)
    # chunk bounds are contiguous, cover [0, num_blocks), and are balanced
    assert plan.chunk_bounds[0][0] == 0
    assert plan.chunk_bounds[-1][1] == plan.num_blocks
    for (a_lo, a_hi), (b_lo, b_hi) in zip(plan.chunk_bounds, plan.chunk_bounds[1:]):
        assert a_hi == b_lo
    widths = [hi - lo for lo, hi in plan.chunk_bounds]
    assert max(widths) - min(widths) <= 1
    # halo map: one right halo block, capped at the axis end
    for (lo, hi), (hlo, hhi) in zip(plan.chunk_bounds, plan.halo_bounds):
        assert hlo == lo
        assert hhi == min(hi + 1, plan.num_blocks)


def test_plan_offsets_are_per_system_element_table():
    plan = build_plan((200, 1000, 5000), 10, num_chunks=2)
    assert plan.offsets == (0, 200, 1200, 6200)
    assert plan.batch == 3
    assert plan.sizes == (200, 1000, 5000)


def test_plan_is_immutable():
    plan = build_plan(100, 10)
    with pytest.raises(AttributeError):
        plan.m = 5


def test_build_plan_validation():
    with pytest.raises(ValueError):
        build_plan((), 10)
    with pytest.raises(ValueError):
        build_plan(55, 10)  # not divisible by m
    with pytest.raises(ValueError):
        build_plan((100, 55), 10)  # one bad system poisons the batch
    with pytest.raises(ValueError):
        build_plan(100, 1)  # m < 2
    with pytest.raises(ValueError):
        build_plan(100, 10, num_chunks=0)
    with pytest.raises(ValueError):
        build_plan(100, 10, num_chunks=2, policy=FixedChunkPolicy(2))


# ---------------------------------------------------------------- policies ---
def test_fixed_chunk_policy():
    plan = build_plan((100, 100), 10, policy=FixedChunkPolicy(4))
    assert plan.num_chunks == 4


def test_policy_pick_below_one_is_clamped_not_fatal():
    """Regression: a fitted heuristic can round to 0 chunks on tiny effective
    sizes; build_plan must clamp a *policy* pick into [1, num_blocks] instead
    of raising and killing the dispatch (explicit num_chunks stays strict)."""
    for bad_k in (0, -3):
        plan = build_plan((60,), 10, policy=FixedChunkPolicy(bad_k))
        assert plan.num_chunks == 1
        assert plan.chunk_bounds == ((0, 6),)
    # the explicit-count contract is unchanged
    with pytest.raises(ValueError):
        build_plan((60,), 10, num_chunks=0)


def test_heuristic_chunk_policy_prices_by_effective_size():
    from repro.core.autotune.heuristic import fit_stream_heuristic
    from repro.core.streams import StreamSimulator

    heur = fit_stream_heuristic(StreamSimulator(seed=1).dataset(reps=2))
    sizes = (2_000_000, 2_000_000, 4_000_000)
    pol = HeuristicChunkPolicy(heur)
    assert pol.num_chunks(sizes, 10) == heur.predict_optimum(float(sum(sizes)))
    plan = build_plan(sizes, 10, policy=pol)
    assert plan.num_chunks == heur.predict_optimum(8_000_000)
    # fp32 halving rule rides along
    pol32 = HeuristicChunkPolicy(heur, fp32=True)
    assert pol32.num_chunks(sizes, 10) == heur.predict_optimum_fp32(8_000_000)


def test_effective_size_accepts_int_and_sequences():
    assert effective_size(500) == 500
    assert effective_size((200, 300)) == 500
    assert effective_size([100] * 5) == 500


# ---------------------------------------------------------------- jit cache --
def test_jitted_stages_cached_per_m():
    s1a, s3a = jitted_stages(10)
    s1b, s3b = jitted_stages(10)
    assert s1a is s1b and s3a is s3b  # no re-jit per solver construction
    s1c, s3c = jitted_stages(5)
    assert s1c is not s1a  # stage 1 closes over m
    assert s3c is s3a  # stage 3 is m-independent: one cached callable for all


def test_solvers_share_cached_stages():
    """Constructing many executors and sessions must not create new jitted
    callables."""
    before = jitted_stages(10)
    for k in (1, 2, 4, 8):
        PlanExecutor("reference")
        TridiagSession(SolverConfig(m=10, num_chunks=k, backend="reference"))
    assert jitted_stages(10) == before


# ---------------------------------------------------------------- executor ---
@pytest.mark.parametrize("num_chunks", [1, 3, 7])
def test_executor_matches_thomas_on_plan(num_chunks):
    n = 400
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=num_chunks)
    plan = build_plan(n, 10, num_chunks=num_chunks)
    x, timing = PlanExecutor().execute(plan, dl, d, du, b)
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-11
    assert timing.num_chunks == num_chunks
    assert timing.t_total_ms > 0


def test_executor_passes_leading_batch_dims_through():
    """The stages are batch-polymorphic; a (B, n) operand set rides one plan."""
    dl, d, du, b, _ = make_diag_dominant_system(240, seed=4, batch=(3,))
    plan = build_plan(240, 10, num_chunks=4)
    x, _ = PlanExecutor().execute(plan, dl, d, du, b)
    assert x.shape == (3, 240)
    for i in range(3):
        assert _rel_err(x[i], thomas_numpy(dl[i], d[i], du[i], b[i])) < 1e-11


def test_executor_rejects_mismatched_operands():
    dl, d, du, b, _ = make_diag_dominant_system(100, seed=0)
    plan = build_plan(200, 10)
    with pytest.raises(ValueError):
        PlanExecutor().execute(plan, dl, d, du, b)
