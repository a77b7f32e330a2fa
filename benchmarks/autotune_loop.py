"""Closed-loop autotune under a size-drifting workload: ``off`` vs ``live``.

The paper fits its stream-count heuristic once, offline, from a measurement
campaign; :mod:`repro.telemetry` closes the loop by refitting it from live
serving telemetry (``SolverConfig.autotune="live"``). This bench serves the
same size-drifting workload twice — once with the loop off, once live — and
reports throughput and dispatch-latency percentiles per mode, so the cost
of *running* the control loop (telemetry recording on the hot path, refits
on the worker's idle time, the atomic policy swap) is a measured number
instead of a hope.

The workload drifts through three request sizes in phases (the queue is
drained between phases, so batch compositions stay closed under
``max_batch`` and every executable pre-warms). The telemetry ring is seeded
with a deterministic synthetic calibration window — a machine where
chunking clearly pays — at effective sizes *disjoint* from the live
traffic's, for two reasons: a cold ``k=1``-only window has no streamed
cells to refit from (a deployment accumulates them from its own history),
and disjoint sizes mean live ``k=1`` cells never shift the seeded medians,
so the first refit is the same fit every run and the CI gate is
reproducible. Live-mode picks then come from the refit heuristic
(provenance ``"refit"``), off-mode picks stay at the serial default.

``--smoke`` (the CI gate) asserts the loop's contract: the refit is
fp-deterministic (two fits of the same window → identical models and
picks), live mode actually refits and swaps (``refits >= 1``, chunked
batches served, provenance ``"refit"``), off mode records and refits
nothing, solved results sit on the fp64 Thomas oracle, and — the headline —
live throughput never degrades more than 10% vs off. Submission is paced
below capacity on purpose: solved/sec is pacing-bound in both modes, so the
gate catches a refit that blocks the worker, not CPU noise. (That the
swapped picks equal ``price_chunks`` of the refit heuristic is hard-asserted
deterministically in tests/test_telemetry.py; here picks are a reported
column, not a gate.)

Usage:
  PYTHONPATH=src python -m benchmarks.run --only autotune_loop
  PYTHONPATH=src python -m benchmarks.autotune_loop --smoke   # CI gate
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from benchmarks import _provenance
from repro.api import (
    BatchObservation,
    FixedChunkPolicy,
    OnlineRefitter,
    SolveRequest,
    SolverConfig,
    TridiagSession,
)
from repro.core.streams.timemodel import STREAM_CANDIDATES
from repro.core.tridiag.plan import price_chunks
from repro.core.tridiag.reference import make_diag_dominant_system, thomas_numpy

#: The drifting request sizes, one serving phase each. Small on purpose:
#: every (composition, chunk-pick) executable the refit can route to —
#: ``STREAM_CANDIDATES`` clamped to the plan's block count — pre-warms in
#: seconds, so the bench measures the control loop, not the XLA compiler.
PHASE_SIZES = (20, 40, 80)
M = 10
MAX_BATCH = 2

#: Seeded calibration window: effective sizes disjoint from anything the
#: live traffic produces (max live effective size = 2 * 80), so live cells
#: never collide with seeded cells and the first refit is deterministic.
SEED_SIZES = (2000, 4000, 8000, 16000)
SEED_KS = (1, 2, 4, 8)
SEED_REPS = 3


def _seed_observations() -> List[BatchObservation]:
    """A deterministic machine where chunking pays at every size.

    Serial latency ``t_non = 1e-3·n`` ms, half of it overlappable; k chunks
    recover ``(k-1)/k`` of the overlappable half minus a small log-in-k
    overhead — so the Eq.-6 gain grows with k and the refit heuristic picks
    k > 1 across the whole size range (including, extrapolated, the small
    live-traffic sizes)."""
    out: List[BatchObservation] = []
    t = 0.0
    for n in SEED_SIZES:
        t_non = 1e-3 * n
        s = 0.5 * t_non
        for k in SEED_KS:
            if k == 1:
                lat = t_non
            else:
                level = math.log2(k)
                lat = t_non - (k - 1) / k * s + 1e-3 * level + 2e-4 * level**2
            for _ in range(SEED_REPS):
                out.append(
                    BatchObservation(
                        t=t,
                        sizes=(n,),
                        num_chunks=k,
                        backend="seed",
                        layout="system-major",
                        latency_ms=lat,
                        mean_wait_ms=0.0,
                        max_wait_ms=0.0,
                    )
                )
                t += 0.01
    return out


def _warm_all_picks() -> None:
    """Compile every (composition, chunk-pick) executable the run can touch.

    The executable cache is process-global, so warming through throwaway
    ``FixedChunkPolicy(k)`` sessions covers the serving run: whatever the
    refit heuristic picks, ``build_plan`` clamps it into the same
    ``STREAM_CANDIDATES``-derived plan set warmed here. A compile mid-run
    would stall dispatch and the gate would measure the compiler."""
    for k in STREAM_CANDIDATES:
        cfg = SolverConfig(
            m=M, max_batch=MAX_BATCH, max_wait_ms=1.0, policy=FixedChunkPolicy(k)
        )
        with TridiagSession(cfg) as session:
            for n in PHASE_SIZES:
                system = make_diag_dominant_system(n, seed=n)[:4]
                for b in range(1, MAX_BATCH + 1):
                    session.solve_many([system] * b)


def _run_mode(
    mode: str,
    seed_obs: List[BatchObservation],
    *,
    per_phase: int,
    pace_us: float,
    refit_interval_s: float,
    oracle_tol: float = 1e-10,
) -> Dict[str, object]:
    """Serve the drifting workload once in ``mode``; return counters.

    The refitter is injected (rather than config-built) so the bench can
    read the refit heuristic's provenance afterwards."""
    refitter: Optional[OnlineRefitter] = None
    if mode != "off":
        refitter = OnlineRefitter(
            mode, min_samples=len(seed_obs), interval_s=refit_interval_s
        )
    cfg = SolverConfig(m=M, max_batch=MAX_BATCH, max_wait_ms=1.0, autotune=mode)
    systems = {
        n: [
            make_diag_dominant_system(n, seed=n * 1000 + i)[:4]
            for i in range(per_phase)
        ]
        for n in PHASE_SIZES
    }
    with TridiagSession(cfg, refitter=refitter) as session:
        if mode != "off":
            for o in seed_obs:
                session.telemetry.record(o)
        # One un-timed warmup request per phase size: wakes the worker so the
        # seeded window's FIRST refit (which pays scipy warm-up) lands before
        # the clock starts — the timed region then measures steady-state
        # loop overhead, the thing the gate is about.
        for n in PHASE_SIZES:
            session.submit(SolveRequest(-n, *systems[n][0])).result(timeout=60.0)

        t0 = time.perf_counter()
        rid = 0
        for n in PHASE_SIZES:
            futs = []
            for i in range(per_phase):
                fut = session.submit(SolveRequest(rid, *systems[n][i]))
                futs.append(fut)
                rid += 1
                if pace_us:
                    time.sleep(pace_us / 1e6)
            # Drain between phases: no mixed-size compositions, so the warm
            # set stays closed.
            for fut in futs:
                fut.result(timeout=60.0)
            # One served result per phase against the fp64 Thomas oracle —
            # an off-oracle serving path is a bug, not a data point.
            dl, d, du, b = systems[n][0]
            ref = thomas_numpy(dl, d, du, b)
            err = float(
                np.max(np.abs(futs[0].result(timeout=0) - ref))
                / (np.max(np.abs(ref)) + 1e-30)
            )
            if err > oracle_tol:
                raise RuntimeError(
                    f"mode={mode} size={n}: served result off the fp64 "
                    f"oracle (rel err {err:.2e})"
                )
        wall = time.perf_counter() - t0
        stats = session.stats
    per_batch = stats["per_batch"]
    # The warmup requests ran pre-t0 at k from the already-swapped policy;
    # drop their batches (one per phase size, recorded first) from the
    # timed-region aggregates.
    timed = per_batch[len(PHASE_SIZES):]
    lat = sorted(pb["latency_ms"] for pb in timed) or [0.0]
    auto = stats["autotune"]
    heur = refitter.last_heuristic() if refitter is not None else None
    return {
        "requests": len(PHASE_SIZES) * per_phase,
        "wall_s": wall,
        "systems_per_sec": len(PHASE_SIZES) * per_phase / wall,
        "p50_ms": lat[len(lat) // 2],
        "p99_ms": lat[min(len(lat) - 1, int(len(lat) * 0.99))],
        "refit_attempts": auto.get("refit_attempts", 0),
        "refits": auto.get("refits", 0),
        "recorded": auto["observations"]["recorded"],
        "picks_gt1": sum(1 for pb in timed if pb["num_chunks"] > 1),
        "provenance": (
            heur.provenance.get("source", "none") if heur is not None else "none"
        ),
        "heuristic": heur,
    }


def autotune_loop(*, per_phase: int = 80, pace_us: float = 4000.0):
    """The bench: one row per autotune mode over the same drifting workload."""
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        _warm_all_picks()
        seed = _seed_observations()
        header = [
            "mode", "requests", "wall_s", "systems_per_sec", "p50_ms",
            "p99_ms", "refits", "recorded", "picks_gt1", "provenance",
        ]
        rows = []
        for mode in ("off", "live"):
            out = _run_mode(
                mode, seed, per_phase=per_phase, pace_us=pace_us,
                refit_interval_s=0.5,
            )
            if out["heuristic"] is not None:
                _provenance.note("autotune_loop", out["heuristic"])
            rows.append([
                mode,
                out["requests"],
                round(out["wall_s"], 3),
                round(out["systems_per_sec"], 1),
                round(out["p50_ms"], 3),
                round(out["p99_ms"], 3),
                out["refits"],
                out["recorded"],
                out["picks_gt1"],
                out["provenance"],
            ])
        return header, rows
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


def smoke() -> None:
    """CI gate: the closed loop's contract, hard-asserted (see module doc)."""
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        seed = _seed_observations()
        # fp-determinism of the refit itself, as a pure function of the
        # window (also warms scipy before anything is timed).
        probe = OnlineRefitter("live", min_samples=1, interval_s=0.0)
        a, b = probe.refit_from(seed), probe.refit_from(seed)
        eff_sizes = sorted(
            {s * k for s in PHASE_SIZES for k in range(1, MAX_BATCH + 1)}
            | set(SEED_SIZES)
        )
        deterministic = (
            a.heuristic is not None
            and b.heuristic is not None
            and np.array_equal(
                a.heuristic.base.sum_model.coef, b.heuristic.base.sum_model.coef
            )
            and a.latency_model.coef == b.latency_model.coef
            and all(
                price_chunks(a.heuristic, (n,)) == price_chunks(b.heuristic, (n,))
                for n in eff_sizes
            )
        )

        _warm_all_picks()
        off = _run_mode(
            "off", seed, per_phase=60, pace_us=4000.0, refit_interval_s=0.4
        )
        live = _run_mode(
            "live", seed, per_phase=60, pace_us=4000.0, refit_interval_s=0.4
        )
        ratio = live["systems_per_sec"] / off["systems_per_sec"]
        checks = [
            ("refit is fp-deterministic", deterministic),
            ("off mode records no telemetry", off["recorded"] == 0),
            ("off mode never refits", off["refits"] == 0),
            ("off mode serves serial picks", off["picks_gt1"] == 0),
            ("live mode refits at least once", live["refits"] >= 1),
            ("live picks carry refit provenance", live["provenance"] == "refit"),
            ("live mode served chunked batches", live["picks_gt1"] >= 1),
            ("live throughput within 10% of off", ratio >= 0.9),
        ]
        failed = [name for name, ok in checks if not ok]
        print(
            f"off={off['systems_per_sec']:.1f}/s "
            f"live={live['systems_per_sec']:.1f}/s ratio={ratio:.3f} "
            f"refits={live['refits']} picks_gt1={live['picks_gt1']} "
            f"provenance={live['provenance']}"
        )
        if failed:
            raise SystemExit(
                f"autotune_loop smoke FAILED: {failed}; "
                f"off={off}, live={live}"
            )
        print(f"SMOKE OK: {len(checks)} closed-loop invariants held")
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="closed-loop contract run asserting determinism, refit-and-swap "
        "and the <=10%% live-vs-off throughput gate (CI gate)",
    )
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    header, rows = autotune_loop()
    print(",".join(str(h) for h in header))
    for r in rows:
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()
