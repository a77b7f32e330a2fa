"""Attention: GQA/MHA with RoPE, qk-norm, logit softcap, local windows,
cross-attention, KV caches — covering every assigned arch's variant.

Compute core is a chunked online-softmax ("flash-style") scan over KV blocks:
the T×T score matrix is never materialized, so 32k prefill and 500k
sequence-sharded decode fit in memory. On the q side the full (per-shard)
block is kept; see EXPERIMENTS.md §Perf for the causal block-skip iteration.

TP layout (DESIGN.md §3): q heads shard over ``model``. KV heads shard over
``model`` when divisible; otherwise (kv_heads < tp, e.g. kimi/qwen3/nemotron)
KV projections+cache replicate across ``model`` and q-head grouping carries
the parallelism — the standard GQA trade.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.models.layers.norms import init_rmsnorm, rms_norm
from repro.models.layers.rotary import apply_rope
from repro.parallel.ctx import ParallelCtx

NEG_INF = -2.0e38


class KVCache(NamedTuple):
    k: jax.Array  # [B, T, KV, hd]
    v: jax.Array  # [B, T, KV, hd]


def init_attention(key, cfg: ArchConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(h * hd)
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * scale_in,
        "wk": jax.random.normal(ks[1], (d, kv * hd), dtype) * scale_in,
        "wv": jax.random.normal(ks[2], (d, kv * hd), dtype) * scale_in,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * scale_out,
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _flash_stats(
    q: jax.Array,      # [B, Sq, KV, G, hd]  (already scaled)
    k: jax.Array,      # [B, T, KV, hd]
    v: jax.Array,      # [B, T, KV, hd]
    q_pos: jax.Array,  # [B, Sq] int32
    k_pos: jax.Array,  # [B, T] int32 (entries past valid length = INT_MAX)
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    kv_chunk: int,
    unroll: bool = False,
):
    b, sq, kvh, g, hd = q.shape
    t = k.shape[1]
    kv_chunk = min(kv_chunk, t)
    n_chunks = -(-t // kv_chunk)
    pad = n_chunks * kv_chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=jnp.iinfo(jnp.int32).max)

    # [n, B, c, ...] chunked views for the scan.
    kc = jnp.moveaxis(k.reshape(b, n_chunks, kv_chunk, kvh, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, kv_chunk, kvh, hd), 1, 0)
    pc = jnp.moveaxis(k_pos.reshape(b, n_chunks, kv_chunk), 1, 0)

    q32 = q.astype(jnp.float32)

    def body(carry, inp):
        m, den, acc = carry
        k_i, v_i, kp_i = inp
        # scores: [B, KV, G, Sq, c]
        s = jnp.einsum(
            "bqkgh,bckh->bkgqc", q32, k_i.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        valid = jnp.ones((b, sq, kv_chunk), dtype=bool)
        if causal:
            valid &= kp_i[:, None, :] <= q_pos[:, :, None]
        if window is not None:
            valid &= q_pos[:, :, None] - kp_i[:, None, :] < window
        valid &= kp_i[:, None, :] < jnp.iinfo(jnp.int32).max  # padding
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        den_new = den * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckh->bkgqh", p, v_i.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, den_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, dtype=jnp.float32)
    den0 = jnp.zeros((b, kvh, g, sq), dtype=jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, sq, hd), dtype=jnp.float32)
    if unroll:  # roofline probe: python loop so every chunk is counted
        carry = (m0, den0, acc0)
        for i in range(n_chunks):
            carry, _ = body(carry, (kc[i], vc[i], pc[i]))
        m, den, acc = carry
    else:
        (m, den, acc), _ = jax.lax.scan(body, (m0, den0, acc0), (kc, vc, pc))
    return m, den, acc


def _finalize(m, den, acc, dtype):
    out = acc / jnp.maximum(den, 1e-30)[..., None]
    # [B, KV, G, Sq, hd] -> [B, Sq, KV, G, hd]
    return jnp.moveaxis(out, 3, 1).astype(dtype)


def _online_attention(q, k, v, q_pos, k_pos, *, causal, window, softcap,
                      kv_chunk, unroll=False):
    m, den, acc = _flash_stats(
        q, k, v, q_pos, k_pos,
        causal=causal, window=window, softcap=softcap, kv_chunk=kv_chunk,
        unroll=unroll,
    )
    return _finalize(m, den, acc, q.dtype)


def _sp_cache_attention(q, k, v, q_pos, k_pos, pctx: ParallelCtx, *,
                        softcap, kv_chunk, seq_axes, batch_axes=()):
    """Sequence-parallel decode attention: the KV cache is sharded along T
    over ``seq_axes``; each shard computes partial online-softmax stats and
    a pmax/psum pair combines them (DESIGN.md §3 SP). Two users:
      long_500k (batch=1): T over the DATA axes;
      kv_heads < tp decode: T over the MODEL axis (batch stays on data) —
        §Perf D1, replacing a cache replicated across ``model``."""
    from jax.sharding import PartitionSpec as P

    seq_axes = tuple(a for a in seq_axes if a in pctx.mesh.axis_names)
    batch_axes = tuple(a for a in batch_axes if a in pctx.mesh.axis_names)
    bspec = batch_axes if batch_axes else None
    unroll = pctx.unroll_attn

    def body(q_b, k_b, v_b, qp_b, kp_b):
        m, den, acc = _flash_stats(
            q_b, k_b, v_b, qp_b, kp_b,
            causal=True, window=None, softcap=softcap,
            kv_chunk=min(kv_chunk, k_b.shape[1]),
            unroll=unroll,
        )
        m_g = jax.lax.pmax(m, seq_axes)
        scale = jnp.exp(m - m_g)
        den_g = jax.lax.psum(den * scale, seq_axes)
        acc_g = jax.lax.psum(acc * scale[..., None], seq_axes)
        return _finalize(m_g, den_g, acc_g, q_b.dtype)

    return shard_map(
        body,
        mesh=pctx.mesh,
        in_specs=(
            P(bspec), P(bspec, seq_axes, None, None),
            P(bspec, seq_axes, None, None),
            P(bspec), P(bspec, seq_axes),
        ),
        out_specs=P(bspec),
        check_vma=False,
    )(q, k, v, q_pos, k_pos)


def attention_apply(
    params: dict,
    x: jax.Array,                     # [B, S, D]
    positions: jax.Array,             # [B, S]
    cfg: ArchConfig,
    pctx: ParallelCtx,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    cache: Optional[KVCache] = None,
    cache_index: Optional[jax.Array] = None,   # [B] write offset into cache
    xattn_kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn K/V src
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Optional[KVCache]]:
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ba = pctx.batch_axes

    q = (x @ params["wq"]).reshape(b, s, h, hd)
    kv_src = xattn_kv[0] if xattn_kv is not None else x
    k = (kv_src @ params["wk"]).reshape(b, -1, kvh, hd)
    v = (kv_src @ params["wv"]).reshape(b, -1, kvh, hd)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    if xattn_kv is None and cfg.num_heads:  # self-attention: RoPE
        if not cfg.is_encdec:  # whisper uses absolute embeddings, no RoPE
            q = apply_rope(q, positions, cfg.rope_theta)
            kv_positions = positions
            k = apply_rope(k, kv_positions, cfg.rope_theta)

    q = pctx.shard(q, ba, None, "model", None)

    # KV sharding: over model iff divisible, else replicated (GQA trade).
    kv_model = "model" if pctx.divisible_by_tp(kvh) else None
    k = pctx.shard(k, ba, None, kv_model, None)
    v = pctx.shard(v, ba, None, kv_model, None)

    new_cache = None
    if cache is not None:
        # decode/continued-prefill: splice new K/V at cache_index.
        t_cache = cache.k.shape[1]
        upd = lambda c, n: jax.vmap(
            lambda cb, nb, ib: jax.lax.dynamic_update_slice_in_dim(cb, nb, ib, axis=0)
        )(c, n.astype(c.dtype), cache_index)
        new_cache = KVCache(k=upd(cache.k, k), v=upd(cache.v, v))
        k, v = new_cache.k, new_cache.v
        k_pos = jnp.broadcast_to(jnp.arange(t_cache, dtype=jnp.int32), (b, t_cache))
    elif cache_index is not None:
        raise ValueError("cache_index without cache")
    else:
        t = k.shape[1]
        if xattn_kv is not None:
            k_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        else:
            k_pos = positions

    # Group q heads per kv head: [B, S, KV, G, hd].
    qg = q.reshape(b, s, kvh, h // kvh, hd) * (1.0 / math.sqrt(hd))
    decode = cache is not None and s == 1 and pctx.mesh is not None
    if decode and pctx.seq_shard:
        out = _sp_cache_attention(
            qg, k, v, positions, k_pos, pctx,
            softcap=cfg.attn_softcap, kv_chunk=kv_chunk,
            seq_axes=pctx.data_axes,
        )
    elif decode and kv_model is None and pctx.tp > 1:
        # §Perf D1: kv_heads < tp would replicate the cache over `model`;
        # shard the cache LENGTH over `model` instead and psum-combine.
        out = _sp_cache_attention(
            qg, k, v, positions, k_pos, pctx,
            softcap=cfg.attn_softcap, kv_chunk=kv_chunk,
            seq_axes=(pctx.model_axis,), batch_axes=pctx.data_axes,
        )
    else:
        out = _online_attention(
            qg, k, v, positions, k_pos,
            causal=causal and xattn_kv is None,
            window=window,
            softcap=cfg.attn_softcap,
            kv_chunk=kv_chunk,
            unroll=pctx.unroll_attn,
        )
    out = out.reshape(b, s, h * hd)
    out = pctx.shard(out, ba, None, "model")
    y = out @ params["wo"]
    return pctx.shard_residual(y), new_cache


def make_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (batch, max_len, kvh, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
