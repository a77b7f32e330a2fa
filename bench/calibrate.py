#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \\
        --seconds 2 [--out FILE]

In one process, for each of ``--seeds`` seeds, one short run of the cell as
the benchmark makes it (its operands, its timed verb at its own size, the
same check), and for each of the first ``--control-seeds`` seeds one more
with the control in the solver's place: the operand kind's ``control``
computed in the precision step below the configuration's dtype (for the
tridiagonal kinds, the Thomas algorithm in bfloat16 on the device). A kind
with no control runs no control seeds, and says so. Prints one JSON line
per run and a summary last: the largest reading of the solver (the lower
reading) and the smallest of the control (the upper). The benchmark's own
runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parent.parent)

from bench.run import start  # noqa: E402

#: The precision step below each configuration dtype, which a control takes.
STEP_BELOW = {"float64": "float32", "float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    manifest, peaks = start(args.workload)
    from bench.harness import run_cell

    config = manifest.config(manifest.workload(args.workload)["config"])
    kind_name = config["operands"]["kind"]
    kind = manifest.operands(kind_name)
    if hasattr(kind, "control"):
        control = kind.control(STEP_BELOW[config["dtype"]])
    else:
        print(f"calibrate: operand kind {kind_name!r} has no control; "
              "no control seeds are run", file=sys.stderr)
        args.control_seeds = 0
    lines = []
    for k in range(args.seeds + args.control_seeds):
        is_control = k >= args.seeds
        seed = args.first_seed + (k - args.seeds if is_control else k)
        t0 = time.perf_counter()
        res = run_cell(
            manifest, args.workload, seed, args.seconds, False, t0, peaks,
            verb_for=(lambda s, name: control) if is_control else None,
        )
        line = {
            "workload": args.workload,
            "side": "control" if is_control else "solver",
            "seed": seed,
            "correct": res["correct"],
            "attempted": res["attempted"],
            "max_rel_err": res["check"]["max_rel_err"]["value"],
            "compared": res["check"]["outputs_compared"]["value"],
            "seconds": time.perf_counter() - t0,
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
    solver = [x["max_rel_err"] for x in lines if x["side"] == "solver"]
    ctl = [x["max_rel_err"] for x in lines if x["side"] == "control"]
    summary = {
        "workload": args.workload,
        "lower": max(solver) if solver else None,
        "upper": min(ctl) if ctl else None,
        "solver_seeds": len(solver),
        "control_seeds": len(ctl),
    }
    if args.out:
        with open(args.out, "w") as f:
            for x in lines + [summary]:
                f.write(json.dumps(x) + "\n")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
