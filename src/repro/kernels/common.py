"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (`pl.pallas_call` + explicit `BlockSpec` VMEM tiling)
and run in fp32; Mosaic has no fp64. On a TPU host they compile to Mosaic;
on any other backend they run with ``interpret=True``, which executes the
kernel body as ordinary JAX ops and checks semantics only (the CPU test
suite runs this way). :func:`interpret_default` is the one switch, and it
follows ``jax.default_backend()`` alone.

Index arithmetic inside a kernel must be int32 whatever ``jax_enable_x64``
says: Mosaic refuses int64 loop counters mixed with int32 slice starts, and
int64 block indices. Kernels therefore build their loops with
:func:`fori_loop` and their block specs with :func:`block_spec`.

The grid dimension provides the automatic HBM→VMEM double-buffered
pipeline that is this repo's analogue of the paper's copy-compute stream
overlap.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Lane width of the TPU vector unit; the trailing tile dim should be a
# multiple of this for full VREG utilization.
LANES = 128
SUBLANES = 8


def interpret_default() -> bool:
    """Interpret mode unless running on a real TPU."""
    return jax.default_backend() != "tpu"


def fori_loop(lo: int, hi: int, body: Callable) -> None:
    """``lax.fori_loop`` with an int32 counter, for effect-only kernel loops."""
    jax.lax.fori_loop(jnp.int32(lo), jnp.int32(hi), body, jnp.int32(0))


def block_spec(block_shape: Sequence, index_map: Callable) -> pl.BlockSpec:
    """``pl.BlockSpec`` whose index map returns int32 block indices."""

    def index_map32(*grid_idx):
        return tuple(jnp.asarray(i, jnp.int32) for i in index_map(*grid_idx))

    return pl.BlockSpec(tuple(block_shape), index_map32)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_axis_to(x, size: int, axis: int, value=0.0):
    """Pad ``axis`` of x up to ``size`` with ``value``."""
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def assert_allclose_by_dtype(actual, desired, dtype) -> None:
    """Tolerance ladder used by every kernel test (oracle comparisons)."""
    tol = {
        "float64": 1e-12,
        "float32": 1e-5,
        "bfloat16": 2e-2,
    }[np.dtype(dtype).name]
    np.testing.assert_allclose(
        np.asarray(actual, np.float64),
        np.asarray(desired, np.float64),
        rtol=tol,
        atol=tol * 10,
    )
