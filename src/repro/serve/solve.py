"""Tridiagonal serving entry points beside the LM serving steps.

The serving story lives in :mod:`repro.core.tridiag.api` (re-exported as
``repro.api``): a :class:`~repro.api.SolverConfig` names the admission knobs
once and :meth:`~repro.api.TridiagSession.submit` returns a
:class:`~repro.api.SolveFuture` resolved by the session's worker thread.
``SolveEngine``, ``SolveRequest`` and ``AdmissionPolicy`` are re-exported
here unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax

from repro.core.tridiag.api import (  # noqa: F401  (re-exports)
    AdmissionPolicy,
    SolveEngine,
    SolveRequest,
)
from repro.core.tridiag.batched import solve_batched


def make_batched_solve_step(m: int = 10) -> Callable:
    """Jitted (B, n) solve step, mirror of ``serve.steps`` step builders."""
    return jax.jit(partial(solve_batched, m=m))
