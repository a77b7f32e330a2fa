#!/usr/bin/env python3
"""Drive the tridiagonal solver's main path once on a TPU and check it.

    python chip_smoke.py [--seed N]     # one chip: the five phases below
    python chip_smoke.py --chips 4      # four chips: sharded vs one device

One chip, every phase in fp32 with ``backend="auto"``, all but the last
through ``TridiagSession``:

  single_1e6 / single_1e7  ``solve`` of one system of 10⁶ and 10⁷ rows
                           (the paper's Table-4 sizes);
  batch_1024x1000          ``solve_batched`` of 1024 systems of 1,000 rows,
                           which must take the interleaved layout;
  served_32                32 requests of 1k–100k rows through ``submit``
                           (``max_batch=8, max_wait_ms=5``);
  timed_1e6                one ``measure.staged_timing``: the paper's
                           per-phase instrument, with its host reduced
                           solve.

``--chips 4`` runs only what exists across chips: the 10⁷-row system on the
system-major sharded path and the 1024-system batch on the lane-sharded
interleaved path, each next to the same solve on one device. Operands are
placed a quarter per device first; the compiled executable refuses operands
placed any other way, so a solve that runs proves the placement.

Every output is checked in fp64 on the host against ``thomas_numpy`` run
on the same fp32 operands. Each phase prints one JSON line; the last line
of standard output is ``{"ok": true, "device": {...}}``. Without a TPU, or
without the ``src/repro`` package beside this file, the script exits
non-zero and prints no result. Everything runs in this one process: a
child process could not reach a chip this process holds.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# Normwise relative error, max|x - x_ref| / max|x_ref|, against the fp64
# oracle on the same fp32 operands. fp32 rounds at 6e-8; the systems are
# strictly diagonally dominant (dominance 2.5), so their condition number is
# a small constant and Thomas and the partition method are backward stable
# on them; the partition method adds the spike and reduced-system
# eliminations, a few tens of roundings per row that do not grow with n.
# 1e-5 leaves two orders of magnitude above that, and an error in the solve
# itself (a wrong block, halo or shard) shows up at O(1).
REL_TOL = 1e-5

SINGLE_SIZES = (10**6, 10**7)
BATCH = (1024, 1000)  # systems, rows each
SERVED_REQUESTS = 32
SERVED_ROWS = (1_000, 100_000)
TIMED_N = 10**6
M = 10


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def check_err(phase: str, err: float) -> None:
    if not err <= REL_TOL:
        fail(f"{phase}: relative error {err} exceeds {REL_TOL}")


def system(n: int, seed: int, batch=()):
    """fp32 operands and their fp64 oracle solution."""
    from repro.core.tridiag import make_diag_dominant_system, thomas_numpy

    dl, d, du, b, _ = make_diag_dominant_system(
        n, seed=seed, batch=batch, dtype=np.float32
    )
    return (dl, d, du, b), thomas_numpy(dl, d, du, b)


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def check_kernels(phase: str, stats: dict) -> None:
    """The solve ran on compiled Pallas kernels, not a fallback."""
    backend = stats["backend"]
    if backend["name"] != "pallas" or backend["interpret"] is not False:
        fail(f"{phase}: resolved backend {backend}, expected compiled pallas")


def report(phase: str, stats: dict, **fields) -> None:
    check_kernels(phase, stats)
    line = {
        "phase": phase,
        **device_info(),
        "backend": stats["backend"]["name"],
        "interpret": stats["backend"]["interpret"],
        "layout": sorted(stats["layout"]),
        "stage2": sorted(stats["stage2"]),
        **fields,
        "limit": REL_TOL,
    }
    print(json.dumps(line), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def first_and_steady(fn):
    """Run ``fn`` twice. The solve verbs return host arrays, so each call
    has finished on the device when it returns. The first call compiles;
    compile seconds are first minus steady."""
    _, first = timed(fn)
    out, steady = timed(fn)
    return out, {"compile_s": first - steady, "solve_s": steady}


# ------------------------------------------------------------- one chip --
def phase_single(n: int, seed: int) -> None:
    from repro.api import SolverConfig, TridiagSession

    ops, ref = system(n, seed)
    with TridiagSession(SolverConfig(m=M, dtype=np.float32)) as s:
        x, times = first_and_steady(lambda: s.solve(*ops))
        stats = s.stats
    err = rel_err(x, ref)
    report(f"single_{n:.0e}".replace("+0", ""), stats, n=n, **times, max_rel_err=err)
    check_err("single", err)


def phase_batch(seed: int) -> None:
    from repro.api import SolverConfig, TridiagSession

    bsz, n = BATCH
    ops, ref = system(n, seed, batch=(bsz,))
    with TridiagSession(SolverConfig(m=M, dtype=np.float32)) as s:
        x, times = first_and_steady(lambda: s.solve_batched(*ops))
        stats = s.stats
    err = rel_err(x, ref)
    report(f"batch_{bsz}x{n}", stats, **times, max_rel_err=err)
    if list(stats["layout"]) != ["interleaved"]:
        fail(f"batch: layout {stats['layout']}, expected interleaved")
    check_err("batch", err)


def phase_served(seed: int) -> None:
    from repro.api import SolveRequest, SolverConfig, TridiagSession

    rng = np.random.default_rng(seed)
    lo, hi = SERVED_ROWS
    sizes = (
        np.exp(rng.uniform(np.log(lo), np.log(hi), SERVED_REQUESTS)) // M * M
    ).astype(int)
    systems = [system(int(n), seed + 1 + i) for i, n in enumerate(sizes)]
    cfg = SolverConfig(m=M, dtype=np.float32, max_batch=8, max_wait_ms=5.0)
    walls, err = [], 0.0
    with TridiagSession(cfg) as s:
        # Two passes over the same requests: the first compiles each batch
        # composition it forms, the second mostly reuses them.
        for rnd in range(2):
            t0 = time.perf_counter()
            futs = [
                s.submit(SolveRequest(rnd * len(systems) + i, *ops))
                for i, (ops, _) in enumerate(systems)
            ]
            xs = [f.result(timeout=900) for f in futs]
            walls.append(time.perf_counter() - t0)
            err = max([err] + [rel_err(x, ref) for x, (_, ref) in zip(xs, systems)])
        stats = s.stats
    report(
        f"served_{SERVED_REQUESTS}",
        stats,
        rows=[int(sizes.min()), int(sizes.max())],
        batches=stats["batches"],
        compile_s=walls[0] - walls[1],
        solve_s=walls[1],
        max_rel_err=err,
    )
    check_err("served", err)


def phase_staged(seed: int) -> None:
    from repro.core.streams.measure import staged_timing
    from repro.core.tridiag import resolve_backend

    ops, ref = system(TIMED_N, seed)
    backend = resolve_backend("auto")
    (x, timing), times = first_and_steady(
        lambda: staged_timing(TIMED_N, *ops, m=M, num_chunks=1, backend=backend)
    )
    stats = {
        "backend": {"name": backend.name, "interpret": backend.interpret_mode()},
        "layout": {timing.layout: 1},
        "stage2": {timing.stage2: 1},
    }
    err = rel_err(x, ref)
    report(
        f"timed_{TIMED_N:.0e}".replace("+0", ""),
        stats,
        **times,
        stage_ms=[timing.t_stage1_ms, timing.t_stage2_ms, timing.t_stage3_ms],
        max_rel_err=err,
    )
    if timing.stage2 != "host":
        fail(f"timed: Stage 2 ran as {timing.stage2}, expected the host solve")
    check_err("timed", err)


def run_one_chip(seed: int) -> None:
    for i, n in enumerate(SINGLE_SIZES):
        phase_single(n, seed + i)
    phase_batch(seed + 10)
    phase_served(seed + 20)
    phase_staged(seed)


# ----------------------------------------------------------- four chips --
def quarter_placed(ops, devices):
    """``device_put`` each operand split along axis 0, a quarter per device,
    and check that each device holds exactly its quarter."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    sharding = NamedSharding(Mesh(np.array(devices), ("x",)), PartitionSpec("x"))
    placed = [jax.device_put(a, sharding) for a in ops]
    for a, host in zip(placed, ops):
        shards = a.addressable_shards
        held = sorted(sh.device.id for sh in shards)
        if held != sorted(dev.id for dev in devices):
            fail(f"operand held on devices {held}")
        rows = host.shape[0] // len(devices)
        for sh in shards:
            if sh.data.shape[0] != rows:
                fail(f"device {sh.device.id} holds {sh.data.shape}, expected {rows} rows")
    return placed


def compare_sharded(
    phase: str, seed: int, n: int, batch: tuple, verb: str, layout: str
) -> None:
    import jax

    from repro.api import SolverConfig, TridiagSession

    devices = jax.devices()[:4]
    ops, ref = system(n, seed, batch=batch)
    with TridiagSession(SolverConfig(m=M)) as one:
        x1, one_times = first_and_steady(lambda: getattr(one, verb)(*ops))
    with TridiagSession(SolverConfig(m=M, mesh=len(devices))) as s:
        # Operands are donated to the solve, so each call places fresh ones.
        x4, times = first_and_steady(
            lambda: getattr(s, verb)(*quarter_placed(ops, devices))
        )
        stats = s.stats
    if stats["mesh"] is None or stats["mesh"]["devices"] != len(devices):
        fail(f"{phase}: session mesh {stats['mesh']}, expected {len(devices)} devices")
    if list(stats["layout"]) != [layout]:
        fail(f"{phase}: layout {stats['layout']}, expected {layout}")
    agree = rel_err(x4, x1)
    err = rel_err(x4, ref)
    report(
        phase,
        stats,
        devices=len(devices),
        **times,
        one_device_solve_s=one_times["solve_s"],
        sharded_vs_one_device=agree,
        max_rel_err=max(err, rel_err(x1, ref)),
    )
    check_err(f"{phase} sharded vs one device", agree)
    check_err(phase, err)


def run_four_chips(seed: int) -> None:
    n = SINGLE_SIZES[-1]
    compare_sharded(
        f"sharded_{n:.0e}".replace("+0", ""), seed, n, (), "solve", "system-major"
    )
    bsz, rows = BATCH
    compare_sharded(
        f"sharded_batch_{bsz}x{rows}", seed + 10, rows, (bsz,), "solve_batched",
        "interleaved",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: run only the sharded phases, each against one device",
    )
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not at {SRC}; run this from a checkout")
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    info = device_info()
    if info["platform"] != "tpu":
        fail(f"no TPU found: jax platform is {info['platform']!r}")
    if info["count"] < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found {info['count']}")
    from repro.core.tridiag import ensure_x64

    ensure_x64()  # the documented entry point: kernels must lower under x64
    print(json.dumps({"jax": jax.__version__, "compile_cache": cache_dir}), flush=True)

    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
