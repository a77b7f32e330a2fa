"""Staged instrument vs fused session: solve latency per (backend × size × chunks).

The paper's core premise is that dispatch overhead — not FLOPs — decides
partition-method latency at small system sizes (it models stream-creation
overhead separately from the non-dominant operation times for exactly this
reason). This bench makes the repo's two executors comparable on that axis:

- **staged** (`measure.staged_timing`, on `PlanExecutor`): per-chunk device
  dispatch from a Python loop plus a host round-trip for the Stage-2 reduced
  solve — the paper's layout, and the one whose per-phase breakdown the
  measurement campaigns consume;
- **fused** (`TridiagSession.solve`, on `FusedExecutor`): the whole
  three-stage solve compiled into ONE donated-buffer XLA dispatch with the
  reduced solve on device.

Every cell is fp64-oracle-checked on BOTH paths before it is timed, and the
row carries the fused:staged speedup. At small sizes (n ≤ ~2560) the staged
path is pure dispatch overhead, so the fused path should win by well over
the 1.5× acceptance floor; at large sizes compute dominates and the gap
narrows. The Pallas backend runs in interpret mode off-TPU — its absolute
numbers demonstrate wiring, not kernel speed.

Usage:
  PYTHONPATH=src python -m benchmarks.run --only dispatch_latency
  PYTHONPATH=src python -m benchmarks.dispatch_latency --smoke   # CI gate
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.streams.measure import staged_timing
from repro.core.tridiag.api import SolverConfig, TridiagSession
from repro.core.tridiag.reference import make_diag_dominant_system, thomas_numpy

#: Sizes where dispatch overhead dominates on this container; the smoke gate
#: asserts the fused path clears this speedup floor on the reference backend.
SMALL_SIZE = 2560
SPEEDUP_FLOOR = 1.5


def dispatch_latency(
    sizes=(640, 1280, 2560, 20_000),
    chunk_counts=(1, 2, 4, 8),
    backends=("reference", "pallas"),
    *,
    m: int = 10,
    reps: int = 5,
    tol: float = 1e-10,
):
    """best-of-reps latency for both dispatch paths + fused:staged speedup.

    Each cell runs the same plan (``m``, ``backend``, ``num_chunks=k``) on
    both executors, and both solutions are checked against the fp64
    ``thomas_numpy`` oracle before timing; an off-oracle path is a bug, not
    a data point.
    """
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        return _dispatch_latency(
            sizes, chunk_counts, backends, m=m, reps=reps, tol=tol
        )
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


def _dispatch_latency(sizes, chunk_counts, backends, *, m, reps, tol):
    header = [
        "backend", "size", "num_chunks", "staged_ms", "fused_ms", "speedup",
        "max_rel_err_staged", "max_rel_err_fused",
    ]
    rows = []
    for n in sizes:
        dl, d, du, b, _ = make_diag_dominant_system(n, seed=0)
        ref = thomas_numpy(dl, d, du, b)
        scale = np.max(np.abs(ref)) + 1e-30
        for backend in backends:
            for k in chunk_counts:
                session = TridiagSession(
                    SolverConfig(m=m, backend=backend, num_chunks=k)
                )
                solvers = {
                    "staged": lambda: staged_timing(
                        n, dl, d, du, b, m=m, num_chunks=k, backend=backend
                    )[0],
                    "fused": lambda: session.solve(dl, d, du, b),
                }
                cell = {}
                for mode, solve in solvers.items():
                    x = solve()  # warmup + oracle probe
                    err = float(np.max(np.abs(x - ref)) / scale)
                    if err > tol:
                        raise RuntimeError(
                            f"{mode} path off fp64 oracle on backend "
                            f"{backend!r}: n={n} k={k} err={err:.2e}"
                        )
                    best = np.inf
                    for _ in range(reps):
                        t0 = time.perf_counter()
                        solve()
                        best = min(best, time.perf_counter() - t0)
                    cell[mode] = (best, err)
                (t_staged, err_s), (t_fused, err_f) = cell["staged"], cell["fused"]
                rows.append([
                    backend, n, k,
                    round(t_staged * 1e3, 3), round(t_fused * 1e3, 3),
                    round(t_staged / t_fused, 2),
                    f"{err_s:.2e}", f"{err_f:.2e}",
                ])
    return header, rows


def check_speedup_floor(rows, *, backend: str = "reference") -> list:
    """Rows on ``backend`` with size ≤ SMALL_SIZE that miss SPEEDUP_FLOOR."""
    return [
        r for r in rows
        if r[0] == backend and r[1] <= SMALL_SIZE and r[5] < SPEEDUP_FLOOR
    ]


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny sweep (CI gate): both paths must pass the fp64 oracle and "
        "fused must clear the small-size speedup floor on the reference "
        "backend",
    )
    args = ap.parse_args()

    if args.smoke:
        header, rows = dispatch_latency(
            sizes=(640, 2560), chunk_counts=(1, 4), backends=("reference",),
            reps=5,
        )
    else:
        header, rows = dispatch_latency()
    print(",".join(str(h) for h in header))
    for r in rows:
        print(",".join(str(x) for x in r))
    slow = check_speedup_floor(rows)
    if args.smoke:
        # Only the CI gate turns the floor into a hard failure; the full run
        # is a measurement sweep and just flags misses.
        if slow:
            raise SystemExit(
                f"fused dispatch under {SPEEDUP_FLOOR}x the staged path at "
                f"small sizes (n <= {SMALL_SIZE}) on the reference backend: "
                f"{slow}"
            )
        print(
            f"SMOKE OK: {len(rows)} cells, fused >= {SPEEDUP_FLOOR}x staged "
            f"at n <= {SMALL_SIZE}, both paths on the fp64 oracle"
        )
    elif slow:
        print(
            f"# WARNING: {len(slow)} cell(s) under the {SPEEDUP_FLOOR}x "
            f"small-size speedup floor: {slow}"
        )


if __name__ == "__main__":
    main()
