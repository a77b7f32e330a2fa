"""CLI for the invariant checker.

::

    python -m repro.analysis check src tests          # what CI runs
    python -m repro.analysis check --select TRD001 src
    python -m repro.analysis list-rules

Exit codes: 0 clean, 1 violations found, 2 usage error (unknown rule code,
no such path).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import List, Optional, Set

from repro.analysis.core import RULES, check_paths, iter_python_files


def _parse_select(raw: Optional[List[str]]) -> Optional[Set[str]]:
    if not raw:
        return None
    codes = {c.strip() for part in raw for c in part.split(",") if c.strip()}
    unknown = codes - set(RULES)
    if unknown:
        known = ", ".join(sorted(RULES))
        raise SystemExit(
            f"error: unknown rule code(s) {sorted(unknown)} (known: {known})"
        )
    return codes


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Repo-specific invariant checker (lock discipline, "
        "donation safety, trace purity, api surface).",
    )
    sub = parser.add_subparsers(dest="command", required=True)
    chk = sub.add_parser("check", help="run the rules over files/directories")
    chk.add_argument("paths", nargs="+", help="files or directories to check")
    chk.add_argument(
        "--select",
        action="append",
        metavar="CODES",
        help="comma-separated rule codes to run (default: all)",
    )
    sub.add_parser("list-rules", help="print the rule table")
    args = parser.parse_args(argv)

    if args.command == "list-rules":
        for code, rule in RULES.items():
            print(f"{code}  {rule.NAME:<20} {rule.SUMMARY}")  # type: ignore[attr-defined]
        return 0

    missing = [p for p in args.paths if not Path(p).exists()]
    if missing:
        print(f"error: no such path(s): {', '.join(missing)}", file=sys.stderr)
        return 2
    try:
        select = _parse_select(args.select)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2

    findings = check_paths(args.paths, select=select)
    for v in findings:
        print(v.format())
    n_files = len(iter_python_files(args.paths))
    if findings:
        print(f"\n{len(findings)} violation(s) in {n_files} file(s) checked")
        return 1
    print(f"repro.analysis: {n_files} file(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
