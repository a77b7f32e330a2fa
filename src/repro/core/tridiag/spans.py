"""Names of the solver's profiler spans and device scopes, defined once.

Host spans are ``jax.profiler.TraceAnnotation`` events on the thread that
calls the solver; they split one call's host time into the steps below.
Device scopes are ``jax.named_scope`` names inside the fused executable; they
land in every device op's HLO ``op_name`` metadata, so a device trace can
tell the stage that issued an op. Neither costs anything but a check when no
profile runs. ``bench/spans.py`` reads them from a profile by these names.

One synchronous verb call emits, nested under its verb span::

    tridiag.fuse      casts and the fusion of the systems into one operand set
    tridiag.lookup    the plan-cache lookup (in the session), then operand
                      canonicalisation, the dtype check and the
                      executable-cache lookup (in the executor)
    tridiag.launch    the executable's call (argument staging, enqueue) and
                      the device-to-host copy queued behind it
    tridiag.wait      the host blocked until the device has finished
    tridiag.fetch     what is left of that copy once the solution is ready
    tridiag.split     the output cast and the split into per-system solutions

``tridiag.compile`` nests in ``tridiag.lookup`` on an executable-cache miss
only, and ``tridiag.batch`` wraps one served batch on the serving worker.
"""

from __future__ import annotations

SOLVE = "tridiag.solve"
SOLVE_BATCHED = "tridiag.solve_batched"
SOLVE_MANY = "tridiag.solve_many"
FUSE = "tridiag.fuse"
LOOKUP = "tridiag.lookup"
LAUNCH = "tridiag.launch"
WAIT = "tridiag.wait"
FETCH = "tridiag.fetch"
SPLIT = "tridiag.split"
COMPILE = "tridiag.compile"
BATCH = "tridiag.batch"

#: The spans one verb call emits under its verb span, in order.
CALL_STEPS = (FUSE, LOOKUP, LAUNCH, WAIT, FETCH, SPLIT)
HOST_SPANS = (SOLVE, SOLVE_BATCHED, SOLVE_MANY, *CALL_STEPS, COMPILE, BATCH)

INTERLEAVE = "tridiag/interleave"
DEINTERLEAVE = "tridiag/deinterleave"
STAGE1 = "tridiag/stage1"
STAGE2 = "tridiag/stage2"
STAGE3 = "tridiag/stage3"
HALO = "tridiag/halo"
REDUCED_GATHER = "tridiag/reduced_gather"

DEVICE_SCOPES = (INTERLEAVE, DEINTERLEAVE, STAGE1, STAGE2, STAGE3, HALO, REDUCED_GATHER)
