"""Checker framework: file discovery, rule dispatch, reporting.

A rule is a module exposing ``CODE``, ``NAME``, ``SUMMARY``, ``FIXIT`` and a
``check(ctx, registry) -> Iterable[Violation]`` over one parsed file; the
api-surface rule additionally exposes ``check_repo(registry)`` (it audits an
*imported* module, not a file). ``check_paths`` is the one entry point both
the CLI and the tests drive.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set

from repro.analysis.pragmas import parse_allow_pragmas
from repro.analysis.registry import DEFAULT_REGISTRY, Registry


@dataclass(frozen=True)
class Violation:
    """One finding: where, which rule, what is wrong, and how to fix it."""

    code: str
    path: str
    line: int
    col: int
    message: str
    fixit: str = ""

    def format(self) -> str:
        text = f"{self.path}:{self.line}:{self.col}: {self.code} {self.message}"
        if self.fixit:
            text += f"\n    fix: {self.fixit}"
        return text


class FileContext:
    """One parsed file plus its pragma map, shared by every rule."""

    def __init__(self, path: str, source: str, tree: ast.Module) -> None:
        self.path = path
        self.source = source
        self.tree = tree
        self.allowed = parse_allow_pragmas(source)

    def is_waived(self, code: str, line: int) -> bool:
        return code in self.allowed.get(line, set())

    def matches_module(self, suffix: str) -> bool:
        """Does this file's (``/``-normalised) path end with ``suffix``?"""
        return self.path.replace("\\", "/").endswith(suffix)


def _load_rules() -> Dict[str, object]:
    from repro.analysis import (
        api_surface,
        donation,
        lock_guard,
        purity,
    )

    modules = (lock_guard, donation, purity, api_surface)
    return {m.CODE: m for m in modules}


#: code -> rule module, in TRD order.
RULES: Dict[str, object] = _load_rules()


def iter_python_files(paths: Sequence[str]) -> List[Path]:
    """Expand files/directories into a sorted list of ``*.py`` files."""
    out: Set[Path] = set()
    for p in paths:
        path = Path(p)
        if path.is_dir():
            out.update(f for f in path.rglob("*.py") if "__pycache__" not in f.parts)
        elif path.suffix == ".py":
            out.add(path)
    return sorted(out)


def check_source(
    source: str,
    path: str = "<string>",
    *,
    registry: Optional[Registry] = None,
    select: Optional[Iterable[str]] = None,
) -> List[Violation]:
    """Run the per-file rules over one source string (the test fixture hook)."""
    registry = DEFAULT_REGISTRY if registry is None else registry
    codes = set(select) if select is not None else set(RULES)
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [
            Violation(
                code="TRD000",
                path=path,
                line=e.lineno or 1,
                col=e.offset or 0,
                message=f"file does not parse: {e.msg}",
                fixit="fix the syntax error; no invariant can be checked",
            )
        ]
    ctx = FileContext(path, source, tree)
    found: List[Violation] = []
    for code, rule in RULES.items():
        if code not in codes or not hasattr(rule, "check"):
            continue
        for v in rule.check(ctx, registry):  # type: ignore[attr-defined]
            if not ctx.is_waived(v.code, v.line):
                found.append(v)
    return found


def check_paths(
    paths: Sequence[str],
    *,
    registry: Optional[Registry] = None,
    select: Optional[Iterable[str]] = None,
) -> List[Violation]:
    """Run every selected rule over ``paths`` (files or directories).

    Per-file rules (TRD001-TRD003) run on each discovered ``*.py`` file;
    repo-level rules (TRD005) run once per invocation. Returns the combined
    findings sorted by location.
    """
    registry = DEFAULT_REGISTRY if registry is None else registry
    codes = set(select) if select is not None else set(RULES)
    found: List[Violation] = []
    for f in iter_python_files(paths):
        try:
            source = f.read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError) as e:
            found.append(
                Violation(
                    code="TRD000",
                    path=str(f),
                    line=1,
                    col=0,
                    message=f"unreadable file: {e}",
                    fixit="make the file readable UTF-8 or remove it",
                )
            )
            continue
        found.extend(check_source(source, str(f), registry=registry, select=codes))
    for code, rule in RULES.items():
        if code in codes and hasattr(rule, "check_repo"):
            found.extend(rule.check_repo(registry))  # type: ignore[attr-defined]
    return sorted(found, key=lambda v: (v.path, v.line, v.col, v.code))
