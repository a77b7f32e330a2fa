"""Periodic (cyclic) tridiagonal systems: ``solve_periodic`` and
``solve_periodic_batched`` through the session, plan and fused executor,
against a dense float64 ``numpy.linalg.solve`` of the assembled cyclic
matrix, which shares no code with the solver.

Tolerance: the systems are solved in float64 and are strictly diagonally
dominant (condition numbers below 10), so the partition method and
Sherman-Morrison agree with the dense solve to a few ulps of the solution;
1e-12 relative leaves three orders of room, and the missing wrap (the same
operands solved as non-periodic) errs by more than 1e-3.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import SolverConfig, TridiagSession  # noqa: E402
from repro.core.tridiag import partition  # noqa: E402
from repro.core.tridiag.plan import (  # noqa: E402
    FusedExecutor,
    PallasBackend,
    PlanExecutor,
    build_plan,
    clear_executable_cache,
    executable_cache_stats,
)
from repro.kernels.periodic.ops import periodic_correction_pallas  # noqa: E402
from repro.kernels.periodic.ref import periodic_correction_ref  # noqa: E402

TOL = 1e-12  # relative, float64; see the module docstring
LELE_ALPHA = 1.0 / 3.0


def cyclic_matrix(dl, d, du) -> np.ndarray:
    n = d.shape[-1]
    a = np.diag(np.asarray(d, np.float64))
    for i in range(n):
        a[i, (i - 1) % n] += dl[i]
        a[i, (i + 1) % n] += du[i]
    return a


def dense(dl, d, du, b) -> np.ndarray:
    """The float64 reference, line by line, of (B, n) operands."""
    return np.stack(
        [np.linalg.solve(cyclic_matrix(dl[k], d[k], du[k]), b[k]) for k in range(d.shape[0])]
    )


def random_cyclic(rng, batch: int, n: int):
    """Strictly diagonally dominant cyclic systems, corners included."""
    dl = rng.uniform(-1, 1, (batch, n))
    du = rng.uniform(-1, 1, (batch, n))
    d = (2.5 + rng.uniform(0, 1, (batch, n))) * np.where(rng.uniform(size=(batch, n)) < 0.5, -1, 1)
    return dl, d, du, rng.uniform(-1, 1, (batch, n))


def lele(rng, batch: int, n: int):
    """Lele's sixth-order compact derivative of a random periodic field."""
    f = rng.uniform(-1, 1, (batch, n))
    h = 2 * np.pi / n
    b = (14 / 9) * (np.roll(f, -1, -1) - np.roll(f, 1, -1)) / (2 * h) + (1 / 9) * (
        np.roll(f, -2, -1) - np.roll(f, 2, -1)
    ) / (4 * h)
    off = np.full((batch, n), LELE_ALPHA)
    return off, np.ones((batch, n)), off.copy(), b


MATRICES = {"random": random_cyclic, "lele": lele}


def rel_err(x, ref) -> float:
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def session(backend: str, layout: str = "auto", **kw) -> TridiagSession:
    return TridiagSession(
        SolverConfig(m=4, backend=backend, layout=layout, dtype=np.float64, **kw)
    )


@pytest.mark.parametrize("matrix", sorted(MATRICES))
@pytest.mark.parametrize("layout", ["system-major", "interleaved"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_batched_matches_the_dense_solve(backend: str, layout: str, matrix: str):
    ops = MATRICES[matrix](np.random.default_rng(1), 5, 48)
    with session(backend, layout) as s:
        x = s.solve_periodic_batched(*ops)
        assert s.stats["layout"] == {layout: 1}
        assert s.stats["periodic"] == 1
    assert x.shape == (5, 48)
    assert rel_err(x, dense(*ops)) < TOL


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_auto_layout_interleaves_a_wide_batch(backend: str):
    ops = lele(np.random.default_rng(2), 40, 32)
    with session(backend) as s:
        x = s.solve_periodic_batched(*ops)
        assert s.stats["layout"] == {"interleaved": 1}
    assert rel_err(x, dense(*ops)) < TOL


@pytest.mark.parametrize("blocks", [1, 2, 3], ids=lambda p: f"P{p}")
@pytest.mark.parametrize("layout", ["system-major", "interleaved"])
def test_few_blocks(layout: str, blocks: int):
    """P = 3 takes Sherman-Morrison; at P <= 2 both neighbours of a block
    are one unknown, and the corners fold into the off-diagonals."""
    ops = random_cyclic(np.random.default_rng(3), 3, 4 * blocks)
    with session("pallas", layout) as s:
        x = s.solve_periodic_batched(*ops)
    assert rel_err(x, dense(*ops)) < TOL


def test_single_system():
    ops = random_cyclic(np.random.default_rng(4), 1, 40)
    with session("pallas") as s:
        x = s.solve_periodic(*(a[0] for a in ops))
        assert s.stats["periodic"] == 1
    assert x.shape == (40,)
    assert rel_err(x, dense(*ops)[0]) < TOL


def test_recursive_stage2(monkeypatch):
    """A reduced system the Thomas kernel is made to refuse (a small
    ``fits``) takes the recursive partition, on A' alone."""
    monkeypatch.setattr(
        PallasBackend, "_thomas_fits", staticmethod(lambda shape, dtype: lambda p: p <= 6)
    )
    clear_executable_cache()
    ops = random_cyclic(np.random.default_rng(5), 3, 4 * 50)
    with session("pallas", "system-major") as s:
        x = s.solve_periodic_batched(*ops)
        assert s.stats["stage2"] == {"partition_recursive": 1}
    clear_executable_cache()
    assert rel_err(x, dense(*ops)) < TOL


@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_the_non_periodic_solve_fails_the_tolerance(matrix: str):
    ops = MATRICES[matrix](np.random.default_rng(6), 4, 48)
    with session("pallas") as s:
        x = s.solve_batched(*ops)
    assert rel_err(x, dense(*ops)) > 1e-3


def test_float32_on_the_kernels():
    """fp32 operands on the Pallas kernels (interpreted here) agree with
    the float64 reference to fp32 rounding."""
    ops = lele(np.random.default_rng(7), 40, 64)
    with TridiagSession(SolverConfig(m=8, backend="pallas", dtype=np.float32)) as s:
        x = s.solve_periodic_batched(*ops)
    assert x.dtype == np.float32
    assert rel_err(x, dense(*ops)) < 1e-5


def test_periodic_and_plain_plans_never_share_an_executable():
    plain, cyclic = build_plan((48,) * 5, 4), build_plan((48,) * 5, 4, periodic=True)
    assert plain != cyclic and cyclic.periodic and cyclic.num_chunks == 1
    assert build_plan((48,) * 5, 4, periodic=True) is cyclic  # the plan LRU keys it
    clear_executable_cache()
    ops = random_cyclic(np.random.default_rng(8), 5, 48)
    with session("pallas") as s:
        s.solve_batched(*ops)
        s.solve_periodic_batched(*ops)
        s.solve_periodic_batched(*ops)
    stats = executable_cache_stats()
    assert (stats["misses"], stats["hits"], stats["size"]) == (2, 1, 2)


def test_jnp_partition_solve():
    ops = random_cyclic(np.random.default_rng(9), 3, 60)
    x = partition.partition_solve(*(jnp.asarray(a) for a in ops), m=5, periodic=True)
    assert rel_err(np.asarray(x), dense(*ops)) < TOL


@pytest.mark.parametrize("axis", [0, -1], ids=["wide", "batched"])
def test_cyclic_solve_on_either_axis(axis: int):
    dl, d, du, b = random_cyclic(np.random.default_rng(10), 6, 9)
    ops = [jnp.asarray(a if axis == -1 else a.T) for a in (dl, d, du, b)]

    def thomas(*rows):  # plain solve along the same axis
        if axis == -1:
            return partition.thomas(*rows)
        return partition.thomas(*(r.T for r in rows)).T

    x = np.asarray(partition.cyclic_solve(thomas, *ops, axis=axis))
    assert rel_err(x if axis == -1 else x.T, dense(dl, d, du, b)) < TOL


@pytest.mark.parametrize(
    "shape, axis", [((64, 300), 0), ((5, 130), 1), ((1, 3), 1), ((3, 1), 0)]
)
def test_correction_kernel_matches_its_reference(shape, axis: int):
    rng = np.random.default_rng(11)
    y, z = (jnp.asarray(rng.uniform(-1, 1, shape)) for _ in range(2))
    beta_shape = (1, shape[1]) if axis == 0 else (shape[0], 1)
    beta = jnp.asarray(rng.uniform(-1, 1, beta_shape))
    got = periodic_correction_pallas(y, z, beta, axis=axis)
    want = periodic_correction_ref(y, z, beta, axis)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-15, atol=1e-15)


def test_what_a_periodic_call_refuses():
    with session("pallas") as s:
        with pytest.raises(ValueError, match="wrap-around"):
            s.solve_periodic_batched(*random_cyclic(np.random.default_rng(12), 2, 42))
        with pytest.raises(ValueError, match="1-D"):
            s.solve_periodic(*random_cyclic(np.random.default_rng(12), 2, 40))
        with pytest.raises(ValueError, match="batch, n"):
            s.solve_periodic_batched(*(a[0] for a in random_cyclic(np.random.default_rng(12), 1, 40)))
    with pytest.raises(ValueError, match="unchunked and unsharded"):
        build_plan((40,) * 2, 4, num_chunks=2, periodic=True)
    with pytest.raises(ValueError, match="same-size"):
        build_plan((40, 80), 4, periodic=True)
    plan = build_plan((40,) * 2, 4, periodic=True)
    ops = [np.ravel(a) for a in random_cyclic(np.random.default_rng(12), 2, 40)]
    with pytest.raises(ValueError, match="fused executor only"):
        PlanExecutor("pallas").execute(plan, *ops)
    x, timing = FusedExecutor("pallas").execute(plan, *ops)
    assert timing.periodic


def test_interleaved_lanes_shard_over_a_mesh(multi_device_count: int):
    """Each device owns whole systems on the lane-sharded interleaved path,
    so the wrap needs no collective."""
    ops = lele(np.random.default_rng(14), 64, 32)
    with session("pallas", "interleaved", mesh=jax.devices()[:2]) as s:
        x = s.solve_periodic_batched(*ops)
    assert rel_err(x, dense(*ops)) < TOL
