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
import re

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
    too large for the kernel's VMEM tiles, so three levels of the partition
    on the Stage-1 and Stage-3 kernels (10⁶ → 10⁵ → 10⁴ → 10³ rows), then
    the Thomas kernel, and no XLA loop."""
    p = N_LARGE // M
    backend = PallasBackend(interpret=False)
    assert backend.reduced_solve_impl((p,), np.float32) == "partition_recursive"
    assert backend.reduced_solve_levels((p,), np.float32, M) == 3
    text = _compile(backend.make_reduced_solve(M), *[_f32(one_chip, p)] * 4).as_text()
    kernels = [
        name.rsplit(".", 1)[0]
        for name in re.findall(
            r"^\s*%?([\w.-]+) = .* custom-call\(.*custom_call_target=\"tpu_custom_call\"",
            text,
            re.M,
        )
    ]
    assert sorted(kernels) == ["_stage1_impl"] * 3 + ["_stage3_impl"] * 3 + ["_thomas_impl"]
    assert " while(" not in text


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


def test_periodic_pencil_executable(one_chip, x64):
    """The periodic fused executable at the cell's shape (16,384 cyclic
    systems of 512 rows, m = 8): the wide kernels with the wrap, the Thomas
    kernel on the two stacked right-hand sides (32,768 lanes), and the
    correction's kernel, named after its wrapper, in the periodic scope."""
    from repro.core.tridiag import spans
    from repro.core.tridiag.plan import _fused_callable, build_plan

    plan = build_plan((512,) * 16384, 8, periodic=True)
    avals = [_f32(one_chip, plan.total_size)] * 4
    fn, stage2 = _fused_callable(
        plan, PallasBackend(interpret=False), True, avals, "interleaved"
    )
    assert stage2 == "thomas_pallas_wide"
    calls = dict(
        (name.rsplit(".", 1)[0], scope)
        for name, scope in re.findall(
            r"^\s*%?([\w.-]+) = .* custom-call\(.*op_name=\"([^\"]*)\"", fn.as_text(), re.M
        )
    )
    assert sorted(calls) == sorted(
        ["_stage1_impl_wide", "_thomas_impl_wide", spans.PERIODIC_KERNEL, "_stage3_impl_wide"]
    )
    assert f"/{spans.STAGE2}/{spans.PERIODIC}/" in calls[spans.PERIODIC_KERNEL]
    assert _bench_trace().op_class(spans.PERIODIC_KERNEL + ".1") == "xla_glue"


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


def _minor_dims(hlo_type: str):
    """The minor (lane) dimension of each array in an HLO result type, e.g.
    ``f32[64,8,16384]{1,0,2:T(8,128)}`` → 8: a layout lists dimensions
    minor to major."""
    for dims, layout in re.findall(r"\w+\[([\d,]*)\]\{([\d,]*)", hlo_type):
        if dims and layout:
            yield int(dims.split(",")[int(layout.split(",")[0])])


@pytest.mark.parametrize(
    "n, bsz, m, periodic, max_bytes",
    [(512, 16384, 8, True, 3e9), (1000, 998, 10, False, 1.06e9)],
    ids=["compact6-pencil", "adi-sweep"],
)
def test_interleave_keeps_systems_on_lanes(one_chip, x64, n, bsz, m, periodic, max_bytes):
    """The uniform interleave and deinterleave at the benchmark's shapes: no
    op in their scopes holds ``m`` on the minor axis (the (8, 128) tiling
    would pad it to 128 lanes), and the executable's bytes accessed stay
    bounded (the m-minor form read 9.47 GB and 1.06 GB)."""
    from repro.core.tridiag import spans
    from repro.core.tridiag.plan import _fused_callable, build_plan

    plan = build_plan((n,) * bsz, m, periodic=periodic)
    avals = [_f32(one_chip, plan.total_size)] * 4
    fn, _ = _fused_callable(plan, PallasBackend(interpret=False), True, avals, "interleaved")
    scoped = re.findall(
        r"^\s*(?:ROOT )?%?([\w.-]+) = (.*?) [\w-]+\(.*op_name=\"[^\"]*/("
        + "|".join(map(re.escape, (spans.INTERLEAVE, spans.DEINTERLEAVE)))
        + r")/",
        fn.as_text(),
        re.M,
    )
    assert {scope for _, _, scope in scoped} == {spans.INTERLEAVE, spans.DEINTERLEAVE}
    padded = [name for name, hlo_type, _ in scoped if m in _minor_dims(hlo_type)]
    assert not padded
    cost = fn.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= max_bytes
