"""Repo-specific static analysis: the invariants tier-1 can only sample.

The codebase carries three classes of invariants that threaded hammer tests
can exercise but never *prove*: lock discipline around the shared plan /
executable LRUs and the serving engine's queue and stats, donation safety
around ``FusedExecutor``'s ``donate_argnums`` operands, and trace purity of
everything staged into jitted/Pallas callables.  The paper's own contribution
is a model that predicts behaviour *before* running (Eq. 4-7 pick the stream
count offline); this package applies the same philosophy to the code itself —
an AST pass that proves the invariant lexically instead of hoping a test
thread interleaving hits the race.

Run it exactly like CI does::

    python -m repro.analysis check src tests

Rules (each has an error code, a one-line fix-it, and declarative
configuration in :mod:`repro.analysis.registry`):

========  ==================  =====================================================
code      name                invariant
========  ==================  =====================================================
TRD001    lock-guard          reads/writes of registered shared state (the plan /
                              executable caches in ``plan.py``, ``SolveEngine``'s
                              queue/stats fields, ``TridiagSession``'s futures
                              table) must occur lexically inside a ``with
                              <registered-guard>:`` block, or in a method on the
                              registry's allowlist (owner-serialised methods).
TRD002    donation-safety     a variable bound to a device array (``jnp.*`` /
                              ``jax.device_put`` / ...) must not be used again
                              after being passed as a donated operand to a
                              ``FusedExecutor.execute`` call site — reuse is a
                              silent use-after-free on the donated buffer.
TRD003    trace-purity        functions traced by ``jax.jit`` / ``pl.pallas_call``
                              (including the callables the fused executor stages)
                              must not call host ops (``np.*`` on traced values,
                              ``time.*``, Python RNG, ``print``) or mutate
                              nonlocal/global state.
TRD005    api-surface         every ``repro.api`` ``__all__`` name resolves and
                              (for classes/functions) carries a docstring; every
                              ``SolverConfig`` field appears in its docstring.
========  ==================  =====================================================

Waivers: a finding is silenced line-by-line with an explicit pragma comment —
``# trd: allow[TRD003]`` (comma-separate several codes). A pragma on its own
line waives the line directly below it. There is no file- or repo-wide
escape hatch on purpose: every waiver is visible at the use site, greppable,
and names the rule it overrides.

The checker is stdlib-only (``ast`` + ``tokenize``), so it runs anywhere the
repo parses — no ruff-plugin machinery, no third-party imports. CI runs it in
the ``invariants`` job beside mypy (the typed core:
``repro.core.tridiag.{api,plan,layout,ragged}`` and this package are held to
``disallow_untyped_defs``).
"""

from repro.analysis.core import (
    RULES,
    FileContext,
    Violation,
    check_paths,
    check_source,
)
from repro.analysis.registry import DEFAULT_REGISTRY, Registry

__all__ = [
    "DEFAULT_REGISTRY",
    "FileContext",
    "RULES",
    "Registry",
    "Violation",
    "check_paths",
    "check_source",
]
