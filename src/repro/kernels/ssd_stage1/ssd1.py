"""SSD Stage-1 (intra-chunk) as a Pallas TPU kernel.

This is the partition method's Stage 1 applied over time (DESIGN.md §2.4):
for each sequence chunk of length Q the kernel produces

  y_diag[q,h,:] = Σ_{k≤q} (C_q·B_k) · exp(cum_q − cum_k) · u[k,h,:]
  state[h,:,n]  = Σ_k      exp(cum_Q − cum_k) · u[k,h,:] ⊗ B[k,n]

i.e. the chunk-local outputs plus the reduced "interface" state handed to the
small Stage-2 recurrence. One grid step owns one (batch × chunk) cell; the
Q×Q score/decay matmuls are MXU-aligned for Q ∈ {128, 256}, and the grid
pipeline double-buffers the HBM→VMEM streams of the next chunk behind the
current chunk's matmuls — the stream-overlap analogue once more.

VMEM per step: u/y [Q,H,P] + b/c [Q,N] + per-head [Q,Q] temporaries; for
Q=256, H=64, P=64, N=128 that is ≈ 4.5 MB fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common


def _ssd1_kernel(u_ref, dac_ref, b_ref, c_ref, y_ref, s_ref, *, q: int, nh: int):
    u = u_ref[0].astype(jnp.float32)          # [Q, H, P]
    dac = dac_ref[0].astype(jnp.float32)      # [Q, H]
    b = b_ref[0].astype(jnp.float32)          # [Q, N]
    c = c_ref[0].astype(jnp.float32)          # [Q, N]

    cum = jnp.cumsum(dac, axis=0)             # [Q, H]
    scores = c @ b.T                          # [Q, Q]
    tril = jnp.tril(jnp.ones((q, q), jnp.bool_))

    for h in range(nh):                        # static unroll over heads
        ch = cum[:, h]
        decay = jnp.exp(jnp.where(tril, ch[:, None] - ch[None, :], -1e30))
        y_ref[0, :, h, :] = ((scores * decay) @ u[:, h, :]).astype(y_ref.dtype)
        dend = jnp.exp(ch[q - 1] - ch)         # [Q]
        s_ref[0, h, :, :] = (
            (u[:, h, :] * dend[:, None]).T @ b
        ).astype(s_ref.dtype)                  # [P, N]


def ssd1_tiled(u, dac, b, c, *, interpret: bool):
    """u: [G, Q, H, P]; dac: [G, Q, H]; b/c: [G, Q, N] with G = batch·chunks.
    Returns (y_diag [G,Q,H,P], states [G,H,P,N])."""
    g, q, nh, p = u.shape
    n = b.shape[-1]
    grid = (g,)
    return pl.pallas_call(
        functools.partial(_ssd1_kernel, q=q, nh=nh),
        grid=grid,
        in_specs=[
            common.block_spec((1, q, nh, p), lambda i: (i, 0, 0, 0)),
            common.block_spec((1, q, nh), lambda i: (i, 0, 0)),
            common.block_spec((1, q, n), lambda i: (i, 0, 0)),
            common.block_spec((1, q, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            common.block_spec((1, q, nh, p), lambda i: (i, 0, 0, 0)),
            common.block_spec((1, nh, p, n), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, q, nh, p), jnp.float32),
            jax.ShapeDtypeStruct((g, nh, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(u, dac, b, c)
