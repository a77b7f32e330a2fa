"""Pallas TPU kernels for the partition method's GPU hot spots.

Five kernels (each with ``ops.py`` jit wrapper and ``ref.py`` pure-jnp oracle):

- ``thomas``           — batched independent Thomas solves (B systems × n rows).
                         Also the device-side Stage-2 reduced solve of the
                         fused dispatch path (`PallasBackend.make_reduced_solve`
                         traces it into the single-dispatch executable, so a
                         fused solve never round-trips to the host; reduced
                         systems too large for its VMEM tiles are first
                         partitioned on the two stage kernels below).
- ``partition_stage1`` — per-block interior elimination producing the three
                         spike solutions (y, v, w); the paper's Stage-1 kernel.
- ``partition_stage3`` — per-block back-substitution; the paper's Stage-3 kernel.
- ``periodic``         — the Sherman–Morrison rank-one update x = y - β z of a
                         cyclic reduced solve (periodic systems; traced into
                         the fused executable by `PallasBackend.periodic_update`).
- ``tridiag_matvec``   — residual matvec r = A·x (verification/benchmark util).

TPU adaptation notes (DESIGN.md §2): the solve dimension is laid out on
*sublanes* (first tile axis) and the batch/block dimension on *lanes* (second
tile axis, multiples of 128), so each recurrence step is a full-width VPU
operation. The grid over the batch/block axis gives Pallas' double-buffered
HBM→VMEM pipeline — the TPU analogue of the CUDA-stream copy/compute overlap
that the paper tunes.
"""

from repro.kernels.thomas.ops import thomas_pallas, thomas_pallas_wide
from repro.kernels.partition_stage1.ops import (
    partition_stage1_pallas,
    partition_stage1_pallas_batched,
    partition_stage1_pallas_wide,
)
from repro.kernels.partition_stage3.ops import (
    partition_stage3_pallas,
    partition_stage3_pallas_batched,
    partition_stage3_pallas_wide,
)
from repro.kernels.periodic.ops import periodic_correction_pallas
from repro.kernels.tridiag_matvec.ops import tridiag_matvec_pallas

__all__ = [
    "thomas_pallas",
    "thomas_pallas_wide",
    "partition_stage1_pallas",
    "partition_stage1_pallas_batched",
    "partition_stage1_pallas_wide",
    "partition_stage3_pallas",
    "partition_stage3_pallas_batched",
    "partition_stage3_pallas_wide",
    "periodic_correction_pallas",
    "tridiag_matvec_pallas",
]
