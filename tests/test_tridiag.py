"""Unit + property tests for the Thomas and partition tridiagonal solvers."""

from functools import partial

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.tridiag import (  # noqa: E402
    SolverConfig,
    TridiagSession,
    make_diag_dominant_system,
    partition_solve,
    partition_stage1,
    partition_stage2,
    partition_stage3,
    thomas,
    thomas_numpy,
    tridiag_matvec,
    tridiag_to_dense,
)
from repro.core.tridiag.partition import (  # noqa: E402
    partition_levels,
    partition_solve_recursive,
)


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


# ---------------------------------------------------------------- Thomas ----
@pytest.mark.parametrize("n", [1, 2, 3, 10, 97, 1000])
def test_thomas_matches_numpy(n):
    dl, d, du, b, x_true = make_diag_dominant_system(n, seed=n)
    x = np.asarray(thomas(jnp.asarray(dl), jnp.asarray(d), jnp.asarray(du), jnp.asarray(b)))
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-12
    assert _rel_err(x, x_true) < 1e-9


@pytest.mark.parametrize("n", [1, 2, 97, 1000])
def test_thomas_numpy_1d_matches_array_loop(n):
    # One system takes the Python-float recurrence; a batch of one takes the
    # array loop. Both are IEEE-double, so they must agree bit for bit.
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=n + 1)
    x1 = thomas_numpy(dl, d, du, b)
    xb = thomas_numpy(dl[None], d[None], du[None], b[None])[0]
    assert x1.dtype == np.float64
    np.testing.assert_array_equal(x1, xb)


def test_thomas_vs_dense_solve():
    dl, d, du, b, _ = make_diag_dominant_system(64, seed=7)
    x_dense = np.linalg.solve(tridiag_to_dense(dl, d, du), b)
    x = np.asarray(thomas(*map(jnp.asarray, (dl, d, du, b))))
    assert _rel_err(x, x_dense) < 1e-12


def test_thomas_batched_and_multirhs():
    dl, d, du, b, _ = make_diag_dominant_system(40, seed=3, batch=(5,))
    x = np.asarray(thomas(*map(jnp.asarray, (dl, d, du, b))))
    for i in range(5):
        assert _rel_err(x[i], thomas_numpy(dl[i], d[i], du[i], b[i])) < 1e-12
    # multi-RHS: trailing axis
    rhs = np.stack([b, 2 * b, -b], axis=-1)
    xm = np.asarray(thomas(*map(jnp.asarray, (dl, d, du)), jnp.asarray(rhs)))
    assert _rel_err(xm[..., 0], x) < 1e-12
    assert _rel_err(xm[..., 1], 2 * x) < 1e-12


def test_thomas_fp32_reasonable():
    dl, d, du, b, x_true = make_diag_dominant_system(256, seed=11, dtype=np.float32)
    x = np.asarray(thomas(*map(jnp.asarray, (dl, d, du, b))))
    assert x.dtype == np.float32
    assert _rel_err(x, x_true) < 1e-4


# ------------------------------------------------------------- partition ----
@pytest.mark.parametrize("n,m", [(20, 10), (100, 10), (64, 2), (60, 3), (1000, 10), (96, 8)])
def test_partition_matches_thomas(n, m):
    dl, d, du, b, x_true = make_diag_dominant_system(n, seed=n + m)
    args = tuple(map(jnp.asarray, (dl, d, du, b)))
    x = np.asarray(partition_solve(*args, m=m))
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-11
    assert _rel_err(x, x_true) < 1e-8


def test_partition_batched():
    dl, d, du, b, _ = make_diag_dominant_system(120, seed=5, batch=(4,))
    x = np.asarray(partition_solve(*map(jnp.asarray, (dl, d, du, b)), m=10))
    ref = thomas_numpy(dl, d, du, b)
    assert _rel_err(x, ref) < 1e-11


def test_partition_reduced_system_is_consistent():
    """Stage-2 unknowns must equal the true solution at block boundaries."""
    n, m = 200, 10
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=2)
    coeffs = partition_stage1(*map(jnp.asarray, (dl, d, du, b)), m=m)
    s = np.asarray(partition_stage2(coeffs))
    x_ref = thomas_numpy(dl, d, du, b)
    np.testing.assert_allclose(s, x_ref[m - 1 :: m], rtol=1e-10, atol=1e-12)


def test_partition_m_must_divide():
    dl, d, du, b, _ = make_diag_dominant_system(20, seed=0)
    with pytest.raises(AssertionError):
        partition_solve(*map(jnp.asarray, (dl, d, du, b)), m=7)


# --------------------------------------------------- recursive partition ----
def _recursive_solve(ops, m, threshold):
    """The recursion on the jnp stages, direct below ``threshold`` rows;
    returns the solution and the row counts each Stage 1 saw."""
    seen = []

    def stage1(*a):
        seen.append(a[1].shape[-1])
        return partition_stage1(*a, m=m)

    solve = jax.jit(partial(
        partition_solve_recursive, m=m, stage1=stage1, stage3=partition_stage3,
        direct=thomas, fits=lambda p: p <= threshold,
    ))
    return np.asarray(solve(*ops)), seen


#: (n, m, threshold, the rows Stage 1 sees at each level after padding)
RECURSION_CASES = [
    (64, 10, 64, []),                    # fits: the direct solve alone
    (1000, 10, 100, [1000]),             # every level a multiple of m
    (1234, 10, 50, [1240, 130]),         # 1234 -> 124 -> 13, both padded
    (997, 10, 8, [1000, 100, 10]),       # 997 -> 100 -> 10 -> 1
    (500, 3, 20, [501, 168, 57]),        # odd m: 500 -> 167 -> 56 -> 19
]


@pytest.mark.parametrize("batch", [(), (3,)], ids=["1d", "batched"])
@pytest.mark.parametrize("n, m, threshold, rows", RECURSION_CASES)
def test_recursive_partition_fp64(n, m, threshold, rows, batch):
    dl, d, du, b, x_true = make_diag_dominant_system(n, seed=n + m, batch=batch)
    x, seen = _recursive_solve((dl, d, du, b), m, threshold)
    assert seen == rows
    assert partition_levels(n, m, lambda p: p <= threshold) == len(rows)
    assert x.shape == d.shape
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-12
    assert _rel_err(x, x_true) < 1e-9


@pytest.mark.parametrize("batch", [(), (3,)], ids=["1d", "batched"])
def test_recursive_partition_fp32_within_table4_limit(batch):
    """Three levels in fp32 against the fp64 solve of the same operands,
    under the benchmark's Table-4 limit (1e-4)."""
    dl, d, du, b, _ = make_diag_dominant_system(
        5003, seed=11, batch=batch, dtype=np.float32
    )
    x, seen = _recursive_solve((dl, d, du, b), 10, 8)
    assert len(seen) == 3 and x.dtype == np.float32
    ref = thomas_numpy(*(a.astype(np.float64) for a in (dl, d, du, b)))
    assert _rel_err(x, ref) < 1e-4


def test_partition_levels_refuses_m_below_two():
    with pytest.raises(ValueError, match="m must be >= 2"):
        partition_levels(100, 1, lambda p: p <= 8)


# The hypothesis-based partition property test lives in test_properties.py
# (skipped cleanly when hypothesis is not installed).


# ---------------------------------------------------------------- chunked ----
def _chunked_session(num_chunks):
    return TridiagSession(
        SolverConfig(m=10, num_chunks=num_chunks, backend="reference")
    )


@pytest.mark.parametrize("num_chunks", [1, 2, 3, 8, 32])
def test_chunked_solver_matches_reference(num_chunks):
    n = 400
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=num_chunks)
    session = _chunked_session(num_chunks)
    x = session.solve(dl, d, du, b)
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-11
    assert session.plan_for(n).num_chunks == min(num_chunks, n // 10)
    assert session.stats["layout"] == {"system-major": 1}


def test_chunked_more_chunks_than_blocks():
    n = 30  # 3 blocks, ask for 8 chunks
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=1)
    session = _chunked_session(8)
    x = session.solve(dl, d, du, b)
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-11
    assert session.plan_for(n).num_chunks == 3
