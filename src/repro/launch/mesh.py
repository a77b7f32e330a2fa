"""Production mesh builders.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is pure data parallelism whose gradient all-reduce crosses the (slower)
inter-pod links; see repro.parallel.collectives for the bucketed overlap.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; smoke tests see
one CPU device). Every model-substrate mesh is built here, with Auto axis
types: ``jax.make_mesh`` defaults to Explicit axes, which
``with_sharding_constraint`` (``ParallelCtx.shard``) refuses.
"""

from __future__ import annotations

from typing import Tuple

import jax

from repro.parallel.ctx import ParallelCtx


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_ctx(mesh, *, seq_shard: bool = False, remat: str = "full",
             strategy: str = "tp") -> ParallelCtx:
    """strategy:
      "tp"      — model axis = tensor/expert parallelism (default)
      "sp_tp"   — TP + Megatron sequence parallelism: the residual stream is
                  seq-sharded over `model`, so per-block activation psums
                  lower to reduce-scatter/all-gather (§Perf Q1c)
      "dp_only" — model axis joins data parallelism; params FSDP-shard over
                  (data, model). Right for small-activation models where TP
                  psums dominate (§Perf Q1a — refuted, see EXPERIMENTS.md)."""
    data_axes: Tuple[str, ...] = tuple(
        a for a in ("pod", "data") if a in mesh.axis_names
    )
    if strategy == "sp_tp":
        return ParallelCtx(
            mesh=mesh,
            data_axes=data_axes,
            model_axis="model",
            fsdp_axis="data",
            seq_shard=seq_shard,
            seq_tp=True,
            remat=remat,
        )
    if strategy == "dp_only":
        return ParallelCtx(
            mesh=mesh,
            data_axes=data_axes + ("model",),
            model_axis=None,
            fsdp_axis=("data", "model"),
            seq_shard=seq_shard,
            remat=remat,
        )
    return ParallelCtx(
        mesh=mesh,
        data_axes=data_axes,
        model_axis="model",
        fsdp_axis="data",
        seq_shard=seq_shard,
        remat=remat,
    )


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for tests run under --xla_force_host_platform_device_count."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
