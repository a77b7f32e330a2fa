"""Benchmark driver: one function per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` style CSV blocks per bench.

Usage:
  PYTHONPATH=src python -m benchmarks.run                 # everything
  PYTHONPATH=src python -m benchmarks.run --only table4   # one bench
  PYTHONPATH=src python -m benchmarks.run --skip-slow     # skip wall-clock benches
  PYTHONPATH=src python -m benchmarks.run --list          # registry (imports all
                                                          # bench modules; CI gate)
  PYTHONPATH=src python -m benchmarks.run --only dispatch_latency \\
      --json BENCH_dispatch.json                          # machine-readable dump

``--json <path>`` writes every selected bench's results as one JSON object
(``{bench: {header, rows, seconds}}`` plus a ``meta`` block with the
timestamp and jax backend), so the perf trajectory can be recorded across
PRs and diffed by tooling instead of eyeballing CSV blocks.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _emit(name: str, header, rows):
    print(f"\n### {name}")
    print(",".join(str(h) for h in header))
    for r in rows:
        print(",".join(str(x) for x in r))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help="run a subset: one bench name or a comma-separated list",
    )
    ap.add_argument("--skip-slow", action="store_true")
    ap.add_argument(
        "--list",
        action="store_true",
        help="print the bench registry and exit (still imports every bench "
        "module, so a broken public entry point fails here)",
    )
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="also write the selected benches' results to PATH as JSON "
        "({bench: {header, rows, seconds}} + a meta block) so perf can be "
        "recorded across PRs",
    )
    args = ap.parse_args()

    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()

    from benchmarks import overlap_autotune, paper_tables

    benches = {
        "table1": paper_tables.table1,
        "table2": paper_tables.table2,
        "table3": paper_tables.table3,
        "table4": paper_tables.table4,
        "table5": paper_tables.table5,
        "fig2": paper_tables.fig2,
        "fig3": paper_tables.fig3,
        "fig4": paper_tables.fig4,
        "a5000": paper_tables.table_a5000,
        "speedup": paper_tables.speedup,
        "grad_buckets": overlap_autotune.gradient_buckets,
        "prefetch_chunks": overlap_autotune.prefetch_chunks,
    }
    slow = {}
    if not args.skip_slow or args.list:
        from benchmarks import (
            arch_steps,
            autotune_loop,
            backend_throughput,
            batched_throughput,
            dispatch_latency,
            ragged_throughput,
            serving_stress,
            sharded_throughput,
        )

        slow = {
            "measured_chunked_solver": overlap_autotune.measured_chunked_solver,
            "batched_throughput": batched_throughput.batched_throughput,
            "ragged_throughput": ragged_throughput.ragged_throughput,
            "backend_throughput": backend_throughput.backend_throughput,
            "dispatch_latency": dispatch_latency.dispatch_latency,
            "serving_stress": serving_stress.serving_stress,
            "arch_steps": arch_steps.arch_step_costs,
            "autotune_loop": autotune_loop.autotune_loop,
            # Degenerates to the single-device baseline unless the process
            # was started with XLA_FLAGS=--xla_force_host_platform_device_count
            # (or on real multi-device hardware); run it standalone via
            # `python -m benchmarks.sharded_throughput` for the full sweep.
            "sharded_throughput": sharded_throughput.sharded_throughput,
        }
    benches.update(slow)

    if args.list:
        for name in benches:
            print(name)
        print(f"# {len(benches)} benches registered")
        return

    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in benches]
        if unknown:
            raise SystemExit(
                f"unknown bench(es) {unknown}; registered: {sorted(benches)}"
            )
        selected = {n: benches[n] for n in names}
    else:
        selected = benches
    results = {}
    for name, fn in selected.items():
        t0 = time.time()
        header, rows = fn()
        _emit(name, header, rows)
        seconds = time.time() - t0
        print(f"# {name} took {seconds:.1f}s")
        results[name] = {
            "header": [str(h) for h in header],
            "rows": [[_jsonable(x) for x in r] for r in rows],
            "seconds": round(seconds, 3),
        }
    if args.json:
        _write_json(args.json, results)
    print("\nALL BENCHES DONE")


def _jsonable(x):
    """Numpy scalars → native Python; anything else non-JSON → str."""
    if hasattr(x, "item"):
        return x.item()
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return str(x)


def _write_json(path: str, results: dict) -> None:
    import datetime

    import jax

    from benchmarks import _provenance

    payload = {
        "meta": {
            "generated_at": datetime.datetime.now(datetime.timezone.utc)
            .isoformat(timespec="seconds"),
            "jax_backend": jax.default_backend(),
            "argv": sys.argv[1:],
            # Which heuristic priced each bench's picks: offline-fit (the
            # simulator campaign) vs refit (serving telemetry), with sample
            # counts — so BENCH_*.json diffs across PRs stay interpretable.
            "heuristic_provenance": _provenance.snapshot(),
        },
        "benches": results,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# wrote {sum(len(b['rows']) for b in results.values())} rows "
          f"across {len(results)} benches to {path}")


if __name__ == "__main__":
    main()
