"""The paper's full ML pipeline, end to end — and applied beyond the paper.

  PYTHONPATH=src python examples/autotune_streams.py

1. Measurement campaign on the calibrated RTX 2080 Ti simulator
   (25 SLAE sizes × {2..32} streams, noisy).
2. Eq. 4 linear regression for ``sum`` (3:1 shuffled split, R²/MSE).
3. Eq. 7 curve-fit overhead models (small/big regimes).
4. Eq. 6 selection vs the Gómez-Luna [6] baseline (Table 1/4 reproduction).
5. The SAME pipeline on real wall-clock solves on THIS machine, through the
   one front door: the fitted heuristic becomes the ChunkPolicy of a
   SolverConfig, and a TridiagSession runs the planned solves — single,
   ragged mixed-size, and async served traffic with deadline admission — so
   one config object flows from autotune fit to serving.
6. Closing the loop: a shadow-mode session refits the SAME pipeline from its
   own serving telemetry on the worker's idle time and reports the would-be
   picks next to the offline fit's (``autotune="live"`` would swap them in).
7. The generalized tuner picking gradient-bucket counts for the LM framework.
"""

import time

import numpy as np

from repro.api import (
    BatchObservation,
    HeuristicChunkPolicy,
    OnlineRefitter,
    SolveRequest,
    SolverConfig,
    TridiagSession,
)
from repro.core.autotune.heuristic import (
    fit_stream_heuristic,
    gomez_luna_optimum,
)
from repro.core.autotune.overlap import tune_gradient_buckets
from repro.core.streams.measure import measure_dataset
from repro.core.streams.simulator import PAPER_SIZES, StreamSimulator
from repro.core.streams.timemodel import sum_overlap
from repro.core.tridiag import ensure_x64
from repro.core.tridiag.plan import price_chunks
from repro.core.tridiag.reference import make_diag_dominant_system, thomas_numpy


def main():
    print("== 1-3) fit the heuristic on the simulated campaign ==")
    sim = StreamSimulator(seed=1)
    heur = fit_stream_heuristic(sim.dataset(reps=2))
    print(f"sum model: {heur.sum_model.coef[0]:.6e} * N + {heur.sum_model.intercept:.4f}"
          f"   (paper Eq.4: 2.189002e-06 * N + 0.1471)")
    for tag in ("sum", "ov_small", "ov_big"):
        tr, te = heur.metrics[f"{tag}_train"], heur.metrics[f"{tag}_test"]
        print(f"{tag:9s} R2 train/test = {tr['r2']:.5f} / {te['r2']:.5f}")

    print("\n== 4) predictions vs actual (paper Table 4) ==")
    hits = 0
    for n in PAPER_SIZES:
        pred, act = heur.predict_optimum(n), sim.actual_optimum(n)
        hits += pred == act
        s = sum_overlap(sim.components(n))
        print(f"N={n:>11,}  pred={pred:2d} actual={act:2d} "
              f"gomez-luna[6]={gomez_luna_optimum(s):6.1f}")
    print(f"-> {hits}/{len(PAPER_SIZES)} exact (paper: 23/25)")

    print("\n== 5) the same pipeline on REAL wall-clock solves (one front door) ==")
    ensure_x64()
    data = measure_dataset((20_000, 100_000, 400_000), (1, 2, 4, 8), reps=2)
    by_size = {}
    for r in data.rows:
        key = r["size"]
        by_size.setdefault(key, []).append((r["num_str"], r["t_str"]))
    for n, runs in sorted(by_size.items()):
        best = min(runs, key=lambda kv: kv[1])
        print(f"N={n:>8,}: best measured chunks on this host = {best[0]} "
              f"({best[1]:.2f} ms)")

    # The fitted heuristic becomes the ChunkPolicy of ONE SolverConfig; the
    # session built from it runs every planned solve below — the policy picks
    # num_chunks from the effective size, build_plan lays out chunk bounds +
    # halo map, and the executor runs the three stages.
    cfg = SolverConfig(
        m=10, policy=HeuristicChunkPolicy(heur), backend="auto",
        max_batch=8, max_wait_ms=5.0,
    )
    with TridiagSession(cfg) as session:
        dl, d, du, b, _ = make_diag_dominant_system(400_000, seed=0)
        t0 = time.perf_counter()
        session.solve(dl, d, du, b)
        print(f"session solve: N=400,000 -> policy picked "
              f"{session.plan_for(400_000).num_chunks} chunks, "
              f"{(time.perf_counter() - t0) * 1e3:.2f} ms wall")

        # Ragged mixed-size fused batch: three heterogeneous systems, one plan.
        mix = (200, 1_000, 5_000)
        systems = [
            make_diag_dominant_system(n, seed=i)[:4] for i, n in enumerate(mix)
        ]
        plan = session.plan_for(mix)
        t0 = time.perf_counter()
        xs = session.solve_many(systems)
        wall_ms = (time.perf_counter() - t0) * 1e3
        err = max(
            float(np.max(np.abs(xi - thomas_numpy(*s))))
            for xi, s in zip(xs, systems)
        )
        print(f"session solve_many: sizes={mix} -> effective "
              f"{plan.effective_size:,}, {plan.num_chunks} chunks, "
              f"{wall_ms:.2f} ms, "
              f"max |err| vs per-system Thomas = {err:.2e}")

        # Served traffic through the SAME config: submit returns futures and
        # the session's worker dispatches at max_batch/the 5 ms deadline —
        # autotune fit to serving, one object, no poll() anywhere. Every
        # served batch runs the FUSED path: one compiled XLA dispatch per
        # batch (device-side reduced solve, donated buffers).
        futs = []
        for rid, n in enumerate((200, 1_000, 5_000, 200, 1_000)):
            system = make_diag_dominant_system(n, seed=10 + rid)[:4]
            futs.append((system, session.submit(SolveRequest(rid, *system))))
        err = max(
            float(np.max(np.abs(fut.result(timeout=30.0) - thomas_numpy(*system))))
            for system, fut in futs
        )
        pb = session.stats["per_batch"][-1]
        print(f"served {len(futs)} requests in {session.stats['batches']} "
              f"single-dispatch fused batch(es); last batch sizes={pb['sizes']} "
              f"({pb['num_chunks']} chunks), max |err| = {err:.2e}")

    print("\n== 6) closed-loop: shadow-mode refit from serving telemetry ==")
    # The paper's fit is a one-shot offline campaign; `repro.telemetry`
    # closes the loop. A shadow session records every served batch into its
    # bounded telemetry ring and refits the SAME Eq. 4-7 pipeline from it on
    # the serve worker's idle time — reporting would-be picks without
    # touching the active policy (autotune="live" swaps it in atomically).
    # The ring is seeded with a synthetic calibration window (a machine
    # where chunking clearly pays) because a cold k=1-only window has no
    # streamed cells to reconstruct Eq. 5 rows from — a deployment
    # accumulates those from its own history.
    demo_sizes = (2_000, 8_000, 32_000)
    refitter = OnlineRefitter("shadow", min_samples=1, interval_s=0.2)
    shadow_cfg = SolverConfig(
        m=10, max_batch=4, max_wait_ms=2.0, autotune="shadow"
    )
    with TridiagSession(shadow_cfg, refitter=refitter) as session:
        t = 0.0
        for n in demo_sizes:
            t_non = 1e-3 * n
            for k in (1, 2, 4, 8):
                level = float(np.log2(k))
                gained = (
                    0.5 * t_non * (k - 1) / k - 0.3 * level - 0.08 * level**2
                    if k > 1 else 0.0
                )
                for _ in range(3):
                    session.telemetry.record(BatchObservation(
                        t=t, sizes=(n,), num_chunks=k, backend="demo",
                        layout="system-major",
                        latency_ms=t_non - gained,
                        mean_wait_ms=0.0, max_wait_ms=0.0,
                    ))
                    t += 0.01
        futs = [
            session.submit(SolveRequest(rid, *make_diag_dominant_system(
                2_000, seed=40 + rid)[:4]))
            for rid in range(3)
        ]
        for fut in futs:
            fut.result(timeout=30.0)
        deadline = time.monotonic() + 5.0
        while (session.stats["autotune"]["refits"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        refit_heur = refitter.last_heuristic()
        auto = session.stats["autotune"]
    if refit_heur is None:
        print("no refit fired within 5 s (thin window) — see stats:", auto)
    else:
        for n in demo_sizes:
            print(f"N={n:>7,}: offline pick = {heur.predict_optimum(n):2d}   "
                  f"refit would pick = {price_chunks(refit_heur, (n,)):2d}")
        print(f"shadow mode: {auto['refits']} refit(s) from "
              f"{auto['observations']['recorded']} observations, "
              f"provenance={refit_heur.provenance.get('source')}, "
              f"agreement with active policy = {auto['agreement_rate']}")

    print("\n== 7) beyond the paper: gradient-bucket tuning (v5e pod) ==")
    for params_b, name in ((4e9, "qwen3-4b"), (340e9, "nemotron-340b")):
        n, margin = tune_gradient_buckets(
            grad_bytes=params_b * 2 / 256,
            link_bandwidth_Bps=50e9,
            backward_compute_s=max(params_b * 4 / 256 / 819e9, 1e-3),
        )
        print(f"{name}: {n} gradient buckets (overlap margin {margin*1e3:.2f} ms)")


if __name__ == "__main__":
    main()
