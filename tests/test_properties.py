"""Hypothesis property tests, split out so the deterministic suites collect
and run even when hypothesis is not installed (requirements-dev.txt has it)."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.tridiag import ensure_x64  # noqa: E402

ensure_x64()

import jax.numpy as jnp  # noqa: E402

from repro.core.autotune.linreg import LinearModel  # noqa: E402
from repro.core.tridiag import (  # noqa: E402
    deinterleave,
    interleave,
    interleave_operands,
    make_diag_dominant_system,
    partition_solve,
    solve_batched,
    thomas_numpy,
    tridiag_matvec,
)


def _rel_err(x, ref):
    return np.max(np.abs(x - ref)) / (np.max(np.abs(ref)) + 1e-30)


@settings(max_examples=25, deadline=None)
@given(
    p=st.integers(min_value=2, max_value=40),
    m=st.integers(min_value=2, max_value=12),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    dominance=st.floats(min_value=1.5, max_value=10.0),
)
def test_property_partition_residual_small(p, m, seed, dominance):
    """For any diagonally dominant system, the residual is tiny and the
    partition solution agrees with Thomas (algorithm-equivalence invariant)."""
    n = p * m
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=seed, dominance=dominance)
    x = np.asarray(partition_solve(*map(jnp.asarray, (dl, d, du, b)), m=m))
    r = tridiag_matvec(dl, d, du, x) - b
    scale = np.max(np.abs(b)) + 1.0
    assert np.max(np.abs(r)) / scale < 1e-9
    assert _rel_err(x, thomas_numpy(dl, d, du, b)) < 1e-8


@settings(max_examples=20, deadline=None)
@given(
    a=st.floats(-5, 5), b=st.floats(-5, 5),
    seed=st.integers(0, 10_000),
)
def test_property_linreg_recovers_noiseless_line(a, b, seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-10, 10, size=30)
    y = a * x + b
    m = LinearModel.fit(x, y)
    assert np.allclose(m.predict(x), y, atol=1e-6 + 1e-6 * abs(a) * 10)


@settings(max_examples=30, deadline=None)
@given(
    bsz=st.integers(min_value=1, max_value=12),
    p_max=st.integers(min_value=1, max_value=10),
    m=st.integers(min_value=2, max_value=8),
    ragged=st.booleans(),
    dtype=st.sampled_from([np.float32, np.float64]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_interleave_roundtrip(bsz, p_max, m, ragged, dtype, seed):
    """deinterleave ∘ interleave is the exact identity on any fused batch
    (uniform and ragged, both dtypes), and ragged padding is identity blocks."""
    rng = np.random.default_rng(seed)
    if ragged:
        ps = rng.integers(1, p_max + 1, size=bsz)
    else:
        ps = np.full(bsz, p_max)
    sizes = tuple(int(q) * m for q in ps)
    a = rng.standard_normal(sum(sizes)).astype(dtype)
    wide = interleave(a, sizes, m)
    assert wide.shape == (int(max(ps)), m, bsz)
    back = np.asarray(deinterleave(wide, sizes, m))
    assert back.dtype == dtype
    np.testing.assert_array_equal(back, a)

    # interleave_operands pads ragged tails with exact identity blocks.
    dlw, dw, duw, bw = (
        np.asarray(w) for w in interleave_operands(a, a, a, a, sizes, m)
    )
    pad = np.ones((int(max(ps)), m, bsz), dtype=bool)
    for i, q in enumerate(ps):
        pad[: int(q), :, i] = False
    assert np.all(dw[pad] == 1.0)
    for w in (dlw, duw, bw):
        assert np.all(w[pad] == 0.0)


@pytest.mark.parametrize("bsz", [1, 33, 130])
@pytest.mark.parametrize("m", [3, 8, 10])
def test_uniform_interleave_is_the_index_map_gather(m, bsz):
    """The uniform branches (reshape/transpose, no gather) equal, bit for
    bit, the ragged branch's definition: the gather through ``_index_maps``'
    ``fwd`` map, and its ``inv`` map back."""
    from repro.core.tridiag.layout import _index_maps

    sizes = (5 * m,) * bsz
    fwd, inv, uniform = _index_maps(sizes, m)
    assert uniform
    rng = np.random.default_rng(1000 * m + bsz)
    a = rng.standard_normal(sum(sizes)).astype(np.float32)
    wide = np.asarray(interleave(a, sizes, m))
    np.testing.assert_array_equal(wide, a[fwd])
    xw = rng.standard_normal(wide.shape).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(deinterleave(xw, sizes, m)), xw.reshape(-1)[inv]
    )


@settings(max_examples=15, deadline=None)
@given(
    bsz=st.integers(min_value=1, max_value=6),
    p=st.integers(min_value=2, max_value=15),
    m=st.integers(min_value=2, max_value=8),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_batched_solve_matches_per_system(bsz, p, m, seed):
    """The batched multi-SLAE solve equals B independent Thomas solves."""
    n = p * m
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=seed, batch=(bsz,))
    x = np.asarray(solve_batched(dl, d, du, b, m=m))
    for i in range(bsz):
        assert _rel_err(x[i], thomas_numpy(dl[i], d[i], du[i], b[i])) < 1e-8
