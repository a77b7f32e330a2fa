"""Quickstart: one config, one session, every way to solve a tridiagonal SLAE.

  PYTHONPATH=src python examples/quickstart.py

The front door is ``repro.api``: a frozen ``SolverConfig`` names the whole
solve configuration once (sub-system size m, backend, chunk policy, admission
knobs) and a ``TridiagSession`` built from it serves every batch shape —

  1. ``solve``          one system (the paper's three-stage partition method),
  2. ``solve_batched``  B same-size systems fused into one dispatch,
  3. ``solve_many``     a ragged mix of sizes fused into one dispatch,
  4. ``submit``         async serving: a SolveFuture resolved by the session's
                        worker thread when the admission deadline fires —
                        no poll() anywhere,

plus the ML heuristic of the paper: fit it on a stream campaign, wrap it in a
``HeuristicChunkPolicy``, and the same session picks the optimum chunk
("virtual stream") count per dispatch.

Every verb (and every served batch) runs the FUSED path — the whole
three-stage solve compiled into one donated-buffer XLA dispatch, reduced
solve on device. The per-phase breakdown the paper's analysis needs comes
from its own instrument, ``repro.core.streams.measure.staged_timing``, which
runs the same plan on the staged per-chunk path. Step 1b below shows both.
"""

import time

import numpy as np

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.api import (  # noqa: E402
    HeuristicChunkPolicy,
    SolveRequest,
    SolverConfig,
    TridiagSession,
)
from repro.configs.paper_tridiag import CONFIG  # noqa: E402
from repro.core.autotune.heuristic import fit_stream_heuristic  # noqa: E402
from repro.core.streams.measure import staged_timing  # noqa: E402
from repro.core.streams.simulator import StreamSimulator  # noqa: E402
from repro.core.tridiag import make_diag_dominant_system, thomas_numpy  # noqa: E402


def main():
    m = CONFIG.sub_system_size
    cfg = SolverConfig(m=m, num_chunks=4, backend="auto", max_wait_ms=10.0)
    print(f"== SolverConfig: m={cfg.m}, backend={cfg.backend!r}, "
          f"num_chunks={cfg.num_chunks}, max_wait_ms={cfg.max_wait_ms} ==")

    n = 100_000
    dl, d, du, b, x_true = make_diag_dominant_system(n, seed=0)

    with TridiagSession(cfg) as session:
        # 1) one system through the chunked partition method: one compiled
        #    XLA dispatch for all three stages, reduced solve on device
        x = session.solve(dl, d, du, b)  # first call compiles the executable
        t0 = time.perf_counter()
        session.solve(dl, d, du, b)
        t_fused_ms = (time.perf_counter() - t0) * 1e3
        print(f"solve         n={n:,}: max|x - x_true| = "
              f"{np.max(np.abs(x - x_true)):.3e}  "
              f"({session.plan_for(n).num_chunks} chunks, {t_fused_ms:.2f} ms)")

        # 1b) the same plan on the paper's per-phase instrument: per-chunk
        #     dispatch with a host round trip for Stage 2, which makes the
        #     phase times observable. A warm rerun keeps compile time out.
        def staged():
            return staged_timing(n, dl, d, du, b, m=m,
                                 num_chunks=cfg.num_chunks, backend=cfg.backend)

        staged()
        _, phases = staged()
        print("phases        stage1/2/3 = "
              + " / ".join(f"{t:.2f}" for t in phases.phases)
              + f" ms; staged {phases.t_total_ms:.2f} ms vs fused "
              f"{t_fused_ms:.2f} ms "
              f"({phases.t_total_ms / max(t_fused_ms, 1e-9):.1f}x)")

        # 2) a batch of same-size systems, fused into one dispatch
        DL, D, DU, B, _ = make_diag_dominant_system(2_000, seed=1, batch=(8,))
        xb = session.solve_batched(DL, D, DU, B)
        err = max(np.max(np.abs(xb[i] - thomas_numpy(DL[i], D[i], DU[i], B[i])))
                  for i in range(8))
        print(f"solve_batched 8 x 2,000:  max err vs Thomas = {err:.3e}")

        # 3) a ragged mix of sizes, still one fused dispatch
        mix = (200, 1_000, 5_000)
        systems = [make_diag_dominant_system(sz, seed=i)[:4]
                   for i, sz in enumerate(mix)]
        xs = session.solve_many(systems)
        err = max(np.max(np.abs(xi - thomas_numpy(*s)))
                  for xi, s in zip(xs, systems))
        print(f"solve_many    mix={mix}:  max err vs Thomas = {err:.3e}")

        # 4) async serving: the future resolves when the 10 ms admission
        #    deadline fires — driven by the session's worker thread, no poll()
        fut = session.submit(SolveRequest(0, dl, d, du, b))
        x0 = fut.result(timeout=30.0)
        pb = session.stats["per_batch"][-1]
        print(f"submit        future resolved after {pb['max_wait_ms']:.1f} ms "
              f"queue wait (deadline {cfg.max_wait_ms} ms), "
              f"max|x - x_true| = {np.max(np.abs(x0 - x_true)):.3e}")

    # 5) the ML heuristic: fit on the calibrated simulator campaign, then let
    #    it pick the chunk count per dispatch through the same front door
    sim = StreamSimulator(seed=1)
    heur = fit_stream_heuristic(sim.dataset(reps=2))
    tuned = SolverConfig(m=m, policy=HeuristicChunkPolicy(heur), backend="auto")
    with TridiagSession(tuned) as session:
        for size in (10_000, 400_000, 1_000_000, 40_000_000):
            pred = heur.predict_optimum(size)
            act = sim.actual_optimum(size)
            plan = session.plan_for(((size + m - 1) // m) * m)
            print(f"size {size:>11,}: policy picks {plan.num_chunks:2d} chunks "
                  f"(predicted {pred:2d}, empirical {act:2d})")


if __name__ == "__main__":
    main()
