"""Decisions the TPU path takes before anything compiles, checked on the CPU:
the Stage-2 choice between the Thomas kernel and the recursive partition,
the refusal of
fp64 on compiled Pallas kernels, the compile-cache location, and
``chip_smoke.py`` refusing to run without a TPU."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro import compile_cache  # noqa: E402
from repro.api import SolverConfig, TridiagSession  # noqa: E402
from repro.core.streams.measure import staged_timing  # noqa: E402
from repro.core.tridiag import make_diag_dominant_system, thomas_numpy  # noqa: E402
from repro.core.tridiag.plan import (  # noqa: E402
    PallasBackend,
    ReferenceBackend,
    resolve_backend,
)
from repro.kernels.thomas.ops import (  # noqa: E402
    VMEM_BUDGET_BYTES,
    thomas_fits_vmem,
    thomas_vmem_bytes,
)

ROOT = Path(__file__).resolve().parent.parent
P_MAX_1D = 4680  # largest fp32 1-D reduced system on one 128-lane tile


# ---------------------------------------------------------- Stage-2 choice --
@pytest.mark.parametrize(
    "p, impl",
    [(P_MAX_1D - 8, "thomas_pallas"), (P_MAX_1D, "thomas_pallas"),
     (P_MAX_1D + 1, "partition_recursive"), (10**6, "partition_recursive")],
)
def test_stage2_rule_at_threshold(p, impl):
    assert PallasBackend().reduced_solve_impl((p,), np.float32) == impl
    assert thomas_fits_vmem(p, 1, 4) == (impl == "thomas_pallas")


@pytest.mark.parametrize(
    "shape, levels",
    [((P_MAX_1D,), 0), ((P_MAX_1D + 1,), 1), ((46_800,), 1), ((10**5,), 2),
     ((10**6,), 3), ((4, 10**5), 2), ((2, 3, 10**5), 0)],
)
def test_stage2_levels_from_the_shape(shape, levels):
    """Depth: the fewest partitions at the plan's m that bring the reduced
    system under the Thomas kernel's VMEM rule; the scan (> 2-D) has none."""
    assert PallasBackend().reduced_solve_levels(shape, np.float32, 10) == levels
    assert ReferenceBackend().reduced_solve_levels(shape, np.float32, 10) == 0


def test_stage2_rule_is_the_kernel_formula():
    # 7 resident (n, block_b) tiles, n in whole 8-row sublane tiles.
    assert thomas_vmem_bytes(P_MAX_1D, 1, 4) == 7 * P_MAX_1D * 128 * 4
    assert thomas_vmem_bytes(P_MAX_1D + 1, 1, 4) == 7 * (P_MAX_1D + 8) * 128 * 4
    assert thomas_vmem_bytes(P_MAX_1D + 1, 1, 4) > VMEM_BUDGET_BYTES
    # Wide lanes take 256-lane blocks: half the rows fit.
    assert thomas_fits_vmem(2336, 1024, 4) and not thomas_fits_vmem(2344, 1024, 4)
    backend = PallasBackend()
    assert backend.wide_reduced_solve_impl((2336, 1024), np.float32) == "thomas_pallas_wide"
    assert backend.wide_reduced_solve_impl((2344, 1024), np.float32) == "thomas_scan_wide"
    # fp64 tiles are twice as wide in bytes: the rule partitions them once.
    assert backend.reduced_solve_impl((P_MAX_1D, ), np.float64) == "partition_recursive"
    assert backend.reduced_solve_levels((P_MAX_1D, ), np.float64, 10) == 1
    # only reduced rows with more than two dimensions keep the scan
    assert backend.reduced_solve_impl((2, 3, 8), np.float32) == "thomas_scan"
    assert ReferenceBackend().reduced_solve_impl((8,), np.float32) == "thomas_scan"


@pytest.mark.parametrize("n, impl", [(46_800, "thomas_pallas"), (46_810, "partition_recursive")])
def test_session_reports_stage2_either_side(n, impl):
    """Both sides of the threshold solve correctly through the fused path,
    and session.stats names the Stage-2 implementation that ran."""
    dl, d, du, b, _ = make_diag_dominant_system(n, seed=3, dtype=np.float32)
    with TridiagSession(SolverConfig(dtype=np.float32, backend="pallas")) as s:
        x = s.solve(dl, d, du, b)
        stats = s.stats
    assert stats["stage2"] == {impl: 1}
    assert stats["layout"] == {"system-major": 1}
    assert stats["backend"] == {"name": "pallas", "interpret": True}
    ref = thomas_numpy(dl, d, du, b)
    assert np.max(np.abs(x - ref)) / np.max(np.abs(ref)) < 1e-5


def test_staged_path_reports_host_stage2():
    dl, d, du, b, _ = make_diag_dominant_system(400, seed=4)
    x, timing = staged_timing(400, dl, d, du, b, m=10, num_chunks=1, backend="auto")
    assert timing.stage2 == "host"
    assert timing.layout == "system-major"
    assert resolve_backend("auto").name == "reference"
    assert np.max(np.abs(x - thomas_numpy(dl, d, du, b))) < 1e-11


# ----------------------------------------------------------- fp64 refusal --
@pytest.fixture
def on_tpu(monkeypatch):
    """Make this host look like a TPU host: "auto" resolves to Pallas and
    its kernels would compile for the chip. Nothing may compile after."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_fp64_dtype_refused_by_validate(on_tpu):
    with pytest.raises(ValueError, match=r"dtype=np\.float32"):
        SolverConfig(dtype=np.float64).validate()
    with pytest.raises(ValueError, match=r"dtype=np\.float32"):
        SolverConfig(dtype=np.float64, backend="pallas").validate()
    SolverConfig(dtype=np.float32).validate()
    SolverConfig(dtype=np.float64, backend="reference").validate()


def test_fp64_operands_refused_by_first_solve(on_tpu):
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=5)
    with TridiagSession(SolverConfig()) as s:
        assert s.stats["backend"] == {"name": "pallas", "interpret": False}
        with pytest.raises(ValueError, match=r"dtype=np\.float32"):
            s.solve(dl, d, du, b)
        with pytest.raises(ValueError, match=r"dtype=np\.float32"):
            staged_timing(200, dl, d, du, b, m=10, num_chunks=1, backend="auto")
        assert s.stats["stage2"] == {}


def test_fp64_runs_on_cpu_pallas_interpret():
    dl, d, du, b, _ = make_diag_dominant_system(200, seed=6)
    with TridiagSession(SolverConfig(backend="pallas")) as s:
        x = s.solve(dl, d, du, b)
    assert np.max(np.abs(x - thomas_numpy(dl, d, du, b))) < 1e-11


# ------------------------------------------------------------ compile cache --
def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.configure_compile_cache()
        assert first == compile_cache.configure_compile_cache()
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


# --------------------------------------------------------------- chip_smoke --
def _run_smoke(script: Path, tmp_path: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env[compile_cache.ENV_VAR] = str(tmp_path / "cache")
    return subprocess.run(
        [sys.executable, str(script)],
        cwd=script.parent, env=env, capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_cpu(tmp_path):
    r = _run_smoke(ROOT / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert r.stdout == ""


def test_chip_smoke_refuses_without_the_repo(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", alone)
    r = _run_smoke(alone / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert r.stdout == ""
