"""Sharded fused execution: parity, cache isolation, and shard_map proof.

Everything here runs on the 8 forced host CPU devices set up by
``tests/conftest.py``. The correctness oracle is two-fold, per the PR-10
acceptance bar: the sharded fused path must match the *unsharded* fused
path (same plan geometry) and the fp64 ``thomas_numpy`` host solve.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from repro.core.tridiag import ensure_x64
from repro.core.tridiag.api import SolverConfig, TridiagSession
from repro.core.tridiag.plan import (
    FusedExecutor,
    build_plan,
    clear_executable_cache,
    executable_cache_stats,
)
from repro.core.tridiag.reference import make_diag_dominant_system, thomas_numpy
from repro.parallel.solver import (
    mesh_signature,
    resolve_mesh_devices,
    shard_count,
)

ensure_x64()

M = 10


def rel_err(x, ref):
    return np.max(np.abs(np.asarray(x) - ref)) / np.max(np.abs(ref))


def tol(dtype):
    return 1e-12 if np.dtype(dtype) == np.float64 else 5e-4


# ------------------------------------------------------------ mesh helpers --
class TestMeshHelpers:
    def test_shard_count_largest_divisor(self):
        assert shard_count(160, 8) == 8
        assert shard_count(10, 8) == 5
        assert shard_count(7, 8) == 7
        assert shard_count(13, 8) == 1  # prime beyond budget -> unsharded
        assert shard_count(100, 1) == 1
        assert shard_count(0, 8) == 1

    def test_resolve_none_and_auto(self, multi_device_count):
        assert resolve_mesh_devices(None) is None
        devices = resolve_mesh_devices("auto")
        assert devices is not None and len(devices) == multi_device_count

    def test_resolve_int(self, multi_device_count):
        assert resolve_mesh_devices(1) is None  # 1 device = unsharded
        devices = resolve_mesh_devices(4)
        assert devices is not None and len(devices) == 4
        with pytest.raises(ValueError, match="visible"):
            resolve_mesh_devices(multi_device_count + 1)
        with pytest.raises(ValueError, match=">= 1"):
            resolve_mesh_devices(0)

    def test_resolve_bad_spec(self):
        with pytest.raises(ValueError, match="auto"):
            resolve_mesh_devices("all")
        with pytest.raises(TypeError):
            resolve_mesh_devices(3.5)

    def test_mesh_signature_identity(self, multi_device_count):
        devices = resolve_mesh_devices("auto")
        assert mesh_signature(None) is None
        sig = mesh_signature(devices)
        assert len(sig) == multi_device_count
        assert sig != mesh_signature(devices[:4])


# ------------------------------------------------------- shard-aligned plans --
class TestShardAlignedPlans:
    def test_chunk_bounds_snap_to_shards(self):
        plan = build_plan(1600, M, num_chunks=12, shards=8)
        assert plan.shards == 8
        assert plan.num_chunks % 8 == 0
        bps = plan.blocks_per_shard
        starts = {lo for lo, _ in plan.chunk_bounds}
        # every shard boundary is a chunk boundary
        assert all(s * bps in starts for s in range(8))

    def test_local_bounds_uniform(self):
        plan = build_plan(1600, M, num_chunks=32, shards=8)
        local = plan.local_chunk_bounds
        cps = plan.num_chunks // plan.shards
        assert len(local) == cps
        bps = plan.blocks_per_shard
        for s in range(plan.shards):
            shard_bounds = plan.chunk_bounds[s * cps : (s + 1) * cps]
            assert tuple(
                (lo - s * bps, hi - s * bps) for lo, hi in shard_bounds
            ) == local

    def test_shards_snap_to_divisor(self):
        # 13 blocks, 8 requested -> largest divisor <= 8 is 1 (13 prime)
        assert build_plan(130, M, num_chunks=4, shards=8).shards == 1
        # 10 blocks, 8 requested -> 5
        assert build_plan(100, M, num_chunks=4, shards=8).shards == 5

    def test_default_plan_unchanged(self):
        assert build_plan(1600, M, num_chunks=12) == build_plan(
            1600, M, num_chunks=12, shards=1
        )

    def test_sharded_and_unsharded_plans_distinct(self):
        assert build_plan(1600, M, num_chunks=8, shards=8) != build_plan(
            1600, M, num_chunks=8
        )

    def test_bad_shards(self):
        with pytest.raises(ValueError, match="shards"):
            build_plan(1600, M, num_chunks=8, shards=0)


# ------------------------------------------------------------------- parity --
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
class TestShardedParity:
    def test_single_system(self, multi_device_count, dtype):
        n = 1600
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=0, dtype=dtype)
        ref = thomas_numpy(dl, d, du, b)
        plan = build_plan(n, M, num_chunks=8, shards=8)
        xu, _ = FusedExecutor(backend="reference", donate=False).execute(
            plan, dl, d, du, b
        )
        xs, _ = FusedExecutor(
            backend="reference", donate=False, mesh="auto"
        ).execute(plan, dl, d, du, b)
        assert rel_err(xs, ref) < tol(dtype)
        # same plan geometry, single vs multi device. fp64 is bit-identical
        # (the halo identity block is exact); fp32 may differ by XLA fusion
        # across the shard_map boundary, so it gets the oracle tolerance.
        if dtype is np.float64:
            np.testing.assert_array_equal(xs, xu)
        else:
            assert np.max(np.abs(xs - xu)) / np.max(np.abs(ref)) < tol(dtype)

    def test_multiple_chunks_per_shard(self, multi_device_count, dtype):
        n = 1600
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=1, dtype=dtype)
        ref = thomas_numpy(dl, d, du, b)
        plan = build_plan(n, M, num_chunks=32, shards=8)
        xu, _ = FusedExecutor(backend="reference", donate=False).execute(
            plan, dl, d, du, b
        )
        xs, _ = FusedExecutor(
            backend="reference", donate=False, mesh="auto"
        ).execute(plan, dl, d, du, b)
        assert rel_err(xs, ref) < tol(dtype)
        if dtype is np.float64:
            np.testing.assert_array_equal(xs, xu)
        else:
            assert np.max(np.abs(xs - xu)) / np.max(np.abs(ref)) < tol(dtype)

    def test_recursive_reduced_solve(self, multi_device_count, dtype):
        """P = 4,682 reduced rows: past the Thomas kernel's VMEM rule, so the
        Pallas backend partitions the replicated reduced solve on every
        device, and the sharded solve still matches the unsharded one."""
        n = 46_820
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=5, dtype=dtype)
        ref = thomas_numpy(dl, d, du, b)
        plan = build_plan(n, M, num_chunks=2, shards=2)
        assert plan.shards == 2
        xu, tu = FusedExecutor(backend="pallas", donate=False).execute(
            plan, dl, d, du, b
        )
        xs, ts = FusedExecutor(backend="pallas", donate=False, mesh=2).execute(
            plan, dl, d, du, b
        )
        assert tu.stage2 == ts.stage2 == "partition_recursive"
        assert rel_err(xs, ref) < tol(dtype)
        if dtype is np.float64:
            np.testing.assert_array_equal(xs, xu)
        else:
            assert np.max(np.abs(xs - xu)) / np.max(np.abs(ref)) < tol(dtype)

    @pytest.mark.parametrize("layout", ["system-major", "interleaved"])
    def test_session_batched(self, multi_device_count, dtype, layout):
        B, n = 64, 320
        DL, D, DU, BB, _ = make_diag_dominant_system(
            n, seed=2, batch=(B,), dtype=dtype
        )
        ref = thomas_numpy(DL, D, DU, BB)
        cfg = SolverConfig(mesh="auto", layout=layout, num_chunks=8)
        with TridiagSession(cfg) as s:
            x = s.solve_batched(DL, D, DU, BB)
        assert np.max(np.abs(x - ref)) / np.max(np.abs(ref)) < tol(dtype)
        cfg0 = SolverConfig(mesh=None, layout=layout, num_chunks=8)
        with TridiagSession(cfg0) as s0:
            x0 = s0.solve_batched(DL, D, DU, BB)
        assert np.max(np.abs(x - x0)) / np.max(np.abs(ref)) < tol(dtype)

    def test_session_ragged(self, multi_device_count, dtype):
        rng = np.random.default_rng(3)
        sizes = [80, 160, 320, 240, 80, 160, 320, 240]
        systems = []
        for i, n in enumerate(sizes):
            dl, d, du, b, _ = make_diag_dominant_system(n, seed=10 + i, dtype=dtype)
            systems.append((dl, d, du, b))
        del rng
        with TridiagSession(SolverConfig(mesh="auto", num_chunks=8)) as s:
            xs = s.solve_many(systems)
        with TridiagSession(SolverConfig(num_chunks=8)) as s0:
            x0 = s0.solve_many(systems)
        for i, (dl, d, du, b) in enumerate(systems):
            ref = thomas_numpy(dl, d, du, b)
            assert rel_err(xs[i], ref) < tol(dtype)
            assert np.max(np.abs(xs[i] - x0[i])) / np.max(np.abs(ref)) < tol(dtype)


class TestShardedParityWide:
    def test_interleaved_batch_shards(self, multi_device_count):
        # 256 lanes / 8 devices = 32 per shard: wide AND sharded under "auto"
        B, n = 256, 160
        DL, D, DU, BB, _ = make_diag_dominant_system(n, seed=4, batch=(B,))
        ref = thomas_numpy(DL, D, DU, BB)
        with TridiagSession(SolverConfig(mesh="auto")) as s:
            x = s.solve_many([tuple(a[i] for a in (DL, D, DU, BB)) for i in range(B)])
        err = max(rel_err(x[i], ref[i]) for i in range(B))
        assert err < 1e-12

    @pytest.mark.parametrize("layout", ["system-major", "interleaved"])
    def test_placed_device_operands(self, multi_device_count, layout):
        # Operands already placed a quarter per device are fused where they
        # live and solve like the same operands passed from the host.
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        B, n = 64, 320
        ops = make_diag_dominant_system(n, seed=5, batch=(B,))[:4]
        devices = jax.devices()[:4]
        sharding = NamedSharding(Mesh(np.array(devices), ("x",)), PartitionSpec("x"))
        cfg = SolverConfig(mesh=4, layout=layout)
        with TridiagSession(cfg) as s:
            x_host = s.solve_batched(*ops)
            placed = [jax.device_put(a, sharding) for a in ops]
            x_placed = s.solve_batched(*placed)
            assert s.stats["layout"] == {layout: 2}
        np.testing.assert_array_equal(x_placed, x_host)
        assert rel_err(x_placed, thomas_numpy(*ops)) < 1e-12

    def test_per_shard_auto_threshold(self, multi_device_count):
        # 64 lanes / 8 devices = 8 per shard < 32: "auto" must NOT interleave
        # under a mesh (per-shard lanes too narrow), though it would at B=64
        # on one device. Observable via the executable working bit-for-bit
        # like the system-major sharded path.
        from repro.core.tridiag.layout import resolve_layout

        assert (
            resolve_layout("auto", (160,) * 64, M, fused=True, batch_shards=8)
            == "system-major"
        )
        assert (
            resolve_layout("auto", (160,) * 64, M, fused=True, batch_shards=1)
            == "interleaved"
        )
        assert (
            resolve_layout("auto", (160,) * 256, M, fused=True, batch_shards=8)
            == "interleaved"
        )


# ------------------------------------------------------------ cache keying --
class TestExecutableCacheIsolation:
    def test_mesh_keys_executables(self, multi_device_count):
        n = 1600
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=5)
        plan = build_plan(n, M, num_chunks=8, shards=8)
        clear_executable_cache()
        ex_u = FusedExecutor(backend="reference", donate=False)
        ex_s = FusedExecutor(backend="reference", donate=False, mesh="auto")
        ex_u.execute(plan, dl, d, du, b)
        assert executable_cache_stats()["size"] == 1
        ex_s.execute(plan, dl, d, du, b)
        # sharded executable must NOT collide with the unsharded one
        assert executable_cache_stats()["size"] == 2
        ex_s.execute(plan, dl, d, du, b)
        assert executable_cache_stats()["hits"] >= 1

    def test_device_subsets_key_separately(self, multi_device_count):
        n = 1600
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=6)
        clear_executable_cache()
        plan4 = build_plan(n, M, num_chunks=8, shards=4)
        FusedExecutor(backend="reference", donate=False, mesh=4).execute(
            plan4, dl, d, du, b
        )
        plan8 = build_plan(n, M, num_chunks=8, shards=8)
        FusedExecutor(backend="reference", donate=False, mesh=8).execute(
            plan8, dl, d, du, b
        )
        assert executable_cache_stats()["size"] == 2


# -------------------------------------------------------- mesh=None identity --
class TestMeshNoneIdentity:
    def test_mesh_none_bit_identical(self):
        n = 1600
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=7)
        plan = build_plan(n, M, num_chunks=8)
        x_ref, _ = FusedExecutor(backend="reference", donate=False).execute(
            plan, dl, d, du, b
        )
        x_none, _ = FusedExecutor(
            backend="reference", donate=False, mesh=None
        ).execute(plan, dl, d, du, b)
        np.testing.assert_array_equal(x_ref, x_none)

    def test_mesh_none_session_stats(self):
        with TridiagSession(SolverConfig(mesh=None)) as s:
            s.solve(*make_diag_dominant_system(100, seed=8)[:4])
            assert s.stats["mesh"] is None

    def test_mesh_auto_session_stats(self, multi_device_count):
        with TridiagSession(SolverConfig(mesh="auto")) as s:
            assert s.stats["mesh"]["devices"] == multi_device_count
            assert s.stats["mesh"]["platform"] == "cpu"


# ------------------------------------------------------------------- config --
class TestConfigValidation:
    def test_mesh_staged_rejected(self):
        with pytest.raises(ValueError, match="staged"):
            SolverConfig(mesh="auto", dispatch="staged").validate()

    def test_mesh_fused_and_auto_ok(self, multi_device_count):
        SolverConfig(mesh="auto", dispatch="fused").validate()
        SolverConfig(mesh="auto", dispatch="auto").validate()
        SolverConfig(mesh=2, dispatch="auto").validate()

    def test_bad_mesh_spec_rejected(self):
        with pytest.raises(ValueError, match="auto"):
            SolverConfig(mesh="everything").validate()

    def test_timed_verbs_fall_back_staged_single_device(self, multi_device_count):
        # dispatch="auto" + mesh: *_timed keeps the staged single-device path
        # (documented fallback) and still matches the oracle.
        n = 800
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=9)
        ref = thomas_numpy(dl, d, du, b)
        with TridiagSession(SolverConfig(mesh="auto", num_chunks=8)) as s:
            x, timing = s.solve_timed(dl, d, du, b)
        assert rel_err(x, ref) < 1e-12
        assert timing.t_stage2_ms >= 0.0  # staged path has a phase breakdown


# ------------------------------------------------------------ shard_map proof --
class TestShardMapProof:
    def test_hlo_contains_collectives(self, multi_device_count):
        """Stage 1/3 provably run under shard_map: the compiled sharded
        executable contains the halo exchange (collective-permute) and the
        reduced-rows all-gather; the unsharded executable contains neither."""
        import jax.numpy as jnp

        from repro.core.tridiag.plan import _fused_callable, resolve_backend

        n = 1600
        plan = build_plan(n, M, num_chunks=8, shards=8)
        avals = [jax.ShapeDtypeStruct((n,), jnp.float64)] * 4
        backend = resolve_backend("reference")
        devices = resolve_mesh_devices("auto")

        sharded, _ = _fused_callable(
            plan, backend, False, avals, "system-major", devices
        )
        hlo = jax.jit(sharded).lower(*avals).compile().as_text()
        assert "all-gather" in hlo
        assert "collective-permute" in hlo

        unsharded, _ = _fused_callable(plan, backend, False, avals, "system-major")
        hlo_u = jax.jit(unsharded).lower(*avals).compile().as_text()
        assert "all-gather" not in hlo_u
        assert "collective-permute" not in hlo_u

    def test_wide_sharded_executable_is_partitioned(self, multi_device_count):
        """The batch-sharded interleaved executable compiles with lane-axis
        sharding (num_partitions > 1) and needs no collectives at all."""
        import jax.numpy as jnp

        from repro.core.tridiag.plan import _fused_callable, resolve_backend

        B, n = 256, 160
        sizes = (n,) * B
        plan = build_plan(sizes, M, num_chunks=1)
        avals = [jax.ShapeDtypeStruct((n * B,), jnp.float64)] * 4
        devices = resolve_mesh_devices("auto")
        wide, _ = _fused_callable(
            plan, resolve_backend("reference"), False, avals, "interleaved", devices
        )
        compiled = jax.jit(wide).lower(*avals).compile()
        assert "sharding" in compiled.as_text()
