"""Mixture-of-Experts with expert parallelism over the ``model`` axis.

Dispatch strategy (DESIGN.md §3): activations are replicated across ``model``
(the TP convention between blocks), so each model shard

  1. computes the (identical) router decision locally,
  2. sort-based-slots the (token, k) assignments into a fixed-capacity
     [E_local, C, D] buffer for its OWN experts only (gather — no all_to_all
     needed because x is replicated over ``model``),
  3. runs the expert FFN as one batched einsum over E_local,
  4. scatter-adds gated outputs back to token positions,

and a single ``psum`` over ``model`` combines the disjoint expert
contributions. Shared experts run as a normal TP-sharded dense MLP outside
the expert-parallel region. Tokens overflowing capacity are dropped (their
residual passes through), the standard capacity-factor trade.

The whole block runs inside ``shard_map`` when a mesh is present; the
identical code path with E_local = E runs plain on a single device.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers.mlp import init_mlp, mlp_apply
from repro.parallel.ctx import ParallelCtx


def init_moe(key, cfg: ArchConfig, dtype) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) / math.sqrt(d),
        "w1": jax.random.normal(ks[1], (e, d, f), dtype) / math.sqrt(d),
        "w3": jax.random.normal(ks[2], (e, d, f), dtype) / math.sqrt(d),
        "w2": jax.random.normal(ks[3], (e, f, d), dtype) / math.sqrt(f),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(
            ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts, "silu_gated", dtype
        )
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _int8_allgather(w, axis: int, axis_name):
    """Tiled all-gather whose payload is int8 (+ per-expert-per-shard fp32
    scales). Backward is the exact adjoint of a tiled all-gather
    (psum-scatter), i.e. a straight-through estimator for the quantization."""
    red_axes = tuple(i for i in range(w.ndim) if i != 0)
    scale = (
        jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)), axis=red_axes), 1e-8)
        / 127.0
    )  # [E_loc]
    q = jnp.clip(
        jnp.round(w.astype(jnp.float32) / scale[(...,) + (None,) * (w.ndim - 1)]),
        -127, 127,
    ).astype(jnp.int8)
    qg = jax.lax.all_gather(q, axis_name, axis=axis, tiled=True)
    sg = jax.lax.all_gather(scale, axis_name)  # [n, E_loc]
    n = sg.shape[0]
    shard = qg.shape[axis] // n
    split = qg.reshape(
        qg.shape[:axis] + (n, shard) + qg.shape[axis + 1:]
    )  # n inserted at position `axis`
    smap_shape = [1] * split.ndim
    smap_shape[0] = sg.shape[1]  # E_loc
    smap_shape[axis] = n
    smap = jnp.moveaxis(sg, 0, 1).reshape(smap_shape)
    deq = split.astype(jnp.float32) * smap
    return deq.reshape(qg.shape).astype(w.dtype)


def _int8_allgather_fwd(w, axis, axis_name):
    return _int8_allgather(w, axis, axis_name), None


def _int8_allgather_bwd(axis, axis_name, _, cot):
    return (
        jax.lax.psum_scatter(
            cot, axis_name, scatter_dimension=axis, tiled=True
        ),
    )


_int8_allgather.defvjp(_int8_allgather_fwd, _int8_allgather_bwd)


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    c = int(
        math.ceil(tokens * cfg.experts_per_token * cfg.capacity_factor / cfg.num_experts)
    )
    return max(8, -(-c // 8) * 8)


def _expert_shard(w1, w3, w2, x_flat, gates, ids, *, cfg: ArchConfig,
                  e_start, capacity: int):
    """Dispatch/compute/combine for one expert shard. x_flat: [T, D];
    gates/ids: [T, K]; w*: [E_loc, ...]. Returns partial y [T, D]."""
    t, d = x_flat.shape
    k = ids.shape[-1]
    e_loc = w1.shape[0]

    flat_ids = ids.reshape(t * k)
    flat_gates = gates.reshape(t * k)
    # Slot assignment: stable sort by expert, then rank within expert.
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(cfg.num_experts))
    pos = jnp.arange(t * k) - seg_start[sorted_ids]
    local = (sorted_ids >= e_start) & (sorted_ids < e_start + e_loc)
    keep = local & (pos < capacity)
    dest = jnp.where(keep, (sorted_ids - e_start) * capacity + pos, e_loc * capacity)
    token_of = order // k

    # Gather tokens into the [E_loc * C (+1 overflow), D] buffer.
    disp = jnp.zeros((e_loc * capacity + 1, d), x_flat.dtype)
    disp = disp.at[dest].set(x_flat[token_of], mode="drop")
    xe = disp[: e_loc * capacity].reshape(e_loc, capacity, d)

    # Batched expert FFN (gated SiLU).
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    g = jnp.einsum("ecd,edf->ecf", xe, w3)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, w2)

    # Combine: route each kept slot's output back to its token, gated.
    vals = jnp.concatenate(
        [ye.reshape(e_loc * capacity, d), jnp.zeros((1, d), ye.dtype)], axis=0
    )
    contrib = vals[dest] * (flat_gates[order] * keep)[:, None].astype(ye.dtype)
    y = jnp.zeros((t, d), ye.dtype).at[token_of].add(contrib)
    return y


def moe_apply(params: dict, x: jax.Array, cfg: ArchConfig, pctx: ParallelCtx):
    """Returns (y, aux_loss). x: [B, S, D]."""
    b, s, d = x.shape
    dtype = x.dtype

    # Router in fp32 (replicated over model — every shard computes the same).
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Load-balancing aux loss (Switch-style): E · Σ_i mean_prob_i · frac_assigned_i.
    me = jnp.mean(probs.reshape(-1, cfg.num_experts), axis=0)
    counts = jax.nn.one_hot(ids.reshape(-1), cfg.num_experts, dtype=jnp.float32).sum(0)
    ce = counts / jnp.maximum(counts.sum(), 1.0)
    aux_loss = cfg.num_experts * jnp.sum(me * ce)

    if pctx.mesh is not None and pctx.tp > 1:
        e_loc = cfg.num_experts // pctx.tp
        tokens_local = (b // max(pctx.dp, 1)) * s
        capacity = _capacity(tokens_local, cfg)

        fsdp = pctx.fsdp_axis

        def gather(w, axis):
            """ZeRO-3 just-in-time gather of [E_loc, ...] expert weights
            (backward = reduce-scatter). With int8_moe_gather the payload
            crosses the mesh quantized with per-(expert, source-shard)
            scales and a straight-through backward — §Perf K1 beyond-paper
            optimization (collective bytes ÷2 vs bf16)."""
            if not pctx.int8_moe_gather:
                return jax.lax.all_gather(w, fsdp, axis=axis, tiled=True)
            return _int8_allgather(w, axis, fsdp)

        def shard_fn(w1, w3, w2, xs, gs, is_):
            if fsdp is not None:
                w1 = gather(w1, 1)
                w3 = gather(w3, 1)
                w2 = gather(w2, 2)
            axis = jax.lax.axis_index(pctx.model_axis)
            tl = xs.shape[0] * xs.shape[1]
            y = _expert_shard(
                w1, w3, w2,
                xs.reshape(tl, d), gs.reshape(tl, -1), is_.reshape(tl, -1),
                cfg=cfg, e_start=axis * e_loc, capacity=capacity,
            )
            return jax.lax.psum(y, pctx.model_axis).reshape(xs.shape)

        ba = pctx.batch_axes
        y = shard_map(
            shard_fn,
            mesh=pctx.mesh,
            in_specs=(
                pctx.spec("model", pctx.fsdp_axis, None),  # w1 [E, D, F]
                pctx.spec("model", pctx.fsdp_axis, None),  # w3
                pctx.spec("model", None, pctx.fsdp_axis),  # w2 [E, F, D]
                pctx.spec(ba, None, None),                 # x
                pctx.spec(ba, None, None),                 # gates
                pctx.spec(ba, None, None),                 # ids
            ),
            out_specs=pctx.spec(ba, None, None),
            check_vma=False,
        )(params["w1"], params["w3"], params["w2"],
          x, gates.astype(dtype), ids)
    else:
        capacity = _capacity(b * s, cfg)
        y = _expert_shard(
            params["w1"], params["w3"], params["w2"],
            x.reshape(b * s, d), gates.astype(dtype).reshape(b * s, -1),
            ids.reshape(b * s, -1),
            cfg=cfg, e_start=0, capacity=capacity,
        ).reshape(b, s, d)

    if cfg.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, "silu_gated", pctx)
    return y.astype(dtype), aux_loss
