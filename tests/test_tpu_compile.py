"""Compile-only checks of the solver's kernels for a described TPU v5e.

Nothing runs here: each test lowers and compiles for one chip of a
``v5e:2x2`` topology that the TPU compiler can describe without the chip.
That finds what interpret mode cannot — Mosaic's lowering rules (int32 index
arithmetic whatever ``jax_enable_x64`` says), VMEM limits and tile
alignment — so every compile runs with x64 on and with x64 off.

Stage 1 and Stage 3 compile as kernels on the tiles their wrappers build
for n = 10⁷: at that size the Stage-1 wrapper's XLA relayouts take about
100 s to compile on a CPU host, which the chip run pays, not this suite.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tridiag.partition import PartitionCoeffs
from repro.core.tridiag.plan import PallasBackend
from repro.kernels.common import round_up
from repro.kernels.partition_stage1.ops import partition_stage1_pallas_wide
from repro.kernels.partition_stage1.stage1 import stage1_tiled
from repro.kernels.partition_stage3.ops import partition_stage3_pallas_wide
from repro.kernels.partition_stage3.stage3 import stage3_tiled
from repro.kernels.thomas.ops import thomas_fits_vmem, thomas_pallas, thomas_pallas_wide

M = 10
N_LARGE = 10**7
BLOCK_P = 512
WIDE = (100, 1024)  # blocks per system, systems


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(params=[True, False], ids=["x64", "x32"])
def x64(request):
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", request.param)
    yield request.param
    jax.config.update("jax_enable_x64", was)


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_stage1_kernel_n1e7(one_chip, x64):
    pp = round_up(N_LARGE // M, BLOCK_P)
    c = _compile(
        lambda *a: stage1_tiled(*a, m=M, block_p=BLOCK_P, interpret=False),
        *[_f32(one_chip, M, pp)] * 4,
    )
    assert _has_kernel(c)


def test_stage3_kernel_n1e7(one_chip, x64):
    pp = round_up(N_LARGE // M, BLOCK_P)
    spikes = [_f32(one_chip, M - 1, pp)] * 3
    rows = [_f32(one_chip, 1, pp)] * 2
    c = _compile(
        lambda *a: stage3_tiled(*a, m=M, block_p=BLOCK_P, interpret=False),
        *spikes,
        *rows,
    )
    assert _has_kernel(c)


def test_thomas_kernel_at_largest_kept_p(one_chip, x64):
    """The 1-D reduced solve at the largest P the Stage-2 rule keeps on the
    kernel (one 128-lane tile of fp32)."""
    p = max(p for p in range(8, 8192) if thomas_fits_vmem(p, 1, 4))
    assert p == 4680
    assert PallasBackend().reduced_solve_impl((p,), np.float32) == "thomas_pallas"
    c = _compile(
        lambda *a: thomas_pallas(*a, interpret=False), *[_f32(one_chip, p)] * 4
    )
    assert _has_kernel(c)


def test_fused_stage2_choice_p1e6(one_chip, x64):
    """The reduced solve the fused executable traces for n = 10⁷ (P = 10⁶):
    too large for the kernel's VMEM tiles, so the on-device scan."""
    p = N_LARGE // M
    backend = PallasBackend(interpret=False)
    assert backend.reduced_solve_impl((p,), np.float32) == "thomas_scan"
    c = _compile(backend.make_reduced_solve(), *[_f32(one_chip, p)] * 4)
    assert not _has_kernel(c)


def test_wide_stage1_kernel(one_chip, x64):
    p, bsz = WIDE
    c = _compile(
        lambda *a: partition_stage1_pallas_wide(*a, m=M, interpret=False),
        *[_f32(one_chip, p, M, bsz)] * 4,
    )
    assert _has_kernel(c)


def test_wide_stage3_kernel(one_chip, x64):
    p, bsz = WIDE

    def stage3(y, v, w, s):
        coeffs = PartitionCoeffs(y, v, w, s, s, s, s)
        return partition_stage3_pallas_wide(coeffs, s, interpret=False)

    spikes = [_f32(one_chip, p, M - 1, bsz)] * 3
    c = _compile(stage3, *spikes, _f32(one_chip, p, bsz))
    assert _has_kernel(c)


def test_wide_thomas_kernel(one_chip, x64):
    p, bsz = WIDE
    backend = PallasBackend(interpret=False)
    assert backend.wide_reduced_solve_impl((p, bsz), np.float32) == "thomas_pallas_wide"
    c = _compile(
        lambda *a: thomas_pallas_wide(*a, interpret=False),
        *[_f32(one_chip, p, bsz)] * 4,
    )
    assert _has_kernel(c)


def _bench_trace():
    """The benchmark's trace reduction, loaded by path (``bench`` is not a
    package of the solver)."""
    import importlib.util
    import sys
    from pathlib import Path

    name = "bench_trace_for_tests"
    if name not in sys.modules:
        path = Path(__file__).resolve().parents[1] / "bench" / "trace.py"
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


@pytest.mark.parametrize(
    "sizes, layout, kernels",
    [
        ((1000,) * 16, "interleaved", ("_stage1_impl_wide", "_thomas_impl_wide", "_stage3_impl_wide")),
        (40_000, "system-major", ("_stage1_impl", "_thomas_impl", "_stage3_impl")),
    ],
    ids=["interleaved", "system-major"],
)
def test_fused_kernels_keep_their_trace_names(one_chip, sizes, layout, kernels):
    """Under the device scopes, the fused executable's Pallas custom calls
    keep the names the benchmark's trace reduction classes them by, and
    each carries its stage's scope."""
    import re

    from repro.core.tridiag import spans
    from repro.core.tridiag.plan import _fused_callable, build_plan

    trace = _bench_trace()
    plan = build_plan(sizes, M, num_chunks=1)
    avals = [_f32(one_chip, plan.total_size)] * 4
    fn, _ = _fused_callable(plan, PallasBackend(interpret=False), True, avals, layout)
    calls = re.findall(
        r"^\s*%?([\w.-]+) = .* custom-call\(.*op_name=\"([^\"]*)\"", fn.as_text(), re.M
    )
    found = {trace.op_name(name).rsplit(".", 1)[0]: scope for name, scope in calls}
    assert tuple(sorted(found)) == tuple(sorted(kernels))
    for kernel, stage, scope in zip(kernels, ("stage1", "stage2", "stage3"),
                                    (spans.STAGE1, spans.STAGE2, spans.STAGE3)):
        assert trace.op_class(kernel + ".1") == stage
        assert f"/{scope}/" in found[kernel]
