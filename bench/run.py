#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. One process does everything: it refuses to
run without a TPU (or with fewer chips than the cell asks for), points JAX
at the checkout's compile cache, makes the cell's operands from the seed,
warms up the cell's own shapes, drives the measured window through the
solver's public verb, checks the outputs against a float64 reference, and
prints one JSON line last on standard output. With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window. The numbers that decide
``correct`` are printed last on standard error, each beside its limit.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def fail(msg: str) -> "None":
    print(f"bench: {msg}", file=sys.stderr)
    raise SystemExit(1)


def start(workload: str):
    """Everything before a cell's set-up: find the solver and the manifest,
    point JAX at the checkout's compile cache, and refuse a host without
    the chips the cell asks for. Returns the manifest and the device's peaks."""
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the solver package is not at {ROOT / 'src'}; run from a checkout")
    # The root, not bench/, heads the path: the benchmark's modules are
    # the package ``bench`` and must not shadow the standard library's.
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench.manifest import Manifest

    manifest = Manifest.load(ROOT)
    cell = manifest.workload(workload)
    config = manifest.config(cell["config"])

    # The compile cache lives at a fixed place inside the checkout, whatever
    # the environment says, and the solver is given it through the variable
    # it follows (repro.compile_cache).
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    # Cache every program, however fast it compiled, so that only a
    # checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_x64", bool(config["x64"]))

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: jax platform is {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        fail(f"{workload} needs {cell['chips']} chips, found {len(devices)}")

    from bench.harness import load_peaks

    try:
        peaks = load_peaks(devices[0].device_kind)
    except KeyError as e:
        fail(str(e))
    return manifest, peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--trace-dir",
        default=None,
        help="keep the profile here (default: a temporary directory, removed)",
    )
    args = ap.parse_args(argv)

    manifest, peaks = start(args.workload)
    from bench.harness import run_cell

    result = run_cell(
        manifest,
        args.workload,
        args.seed,
        args.seconds,
        bool(args.trace),
        T_PROCESS,
        peaks,
        trace_dir=args.trace_dir,
    )
    for name, v in result["check"].items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
