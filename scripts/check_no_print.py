#!/usr/bin/env python
"""Lint: no bare ``print()`` in npairloss_tpu/ library code.

Library modules must emit through the package loggers or the obs metric
sinks (docs/OBSERVABILITY.md) — a print() in library code bypasses both
the embedder's logging configuration and the structured telemetry
pipeline.  The user-facing surfaces are exempt: ``cli.py`` and
``__main__.py`` (their printed JSON lines ARE the product), plus
everything outside the package (scripts/, tests/, benchmarks/).

Exit 0 when clean; exit 1 listing every offending file:line.

Usage: check_no_print.py [ROOT]   (default: the repo's npairloss_tpu/)
"""

from __future__ import annotations

import ast
import os
import sys

EXEMPT_BASENAMES = {"cli.py", "__main__.py"}
# Root-relative exemptions for user-facing surfaces that are not
# top-level: the staticcheck driver's printed findings ARE the product
# (it doubles as `python -m npairloss_tpu staticcheck`).
EXEMPT_RELPATHS = {os.path.join("analysis", "runner.py")}


def find_prints(path: str):
    """Yield (lineno, source_line) for every print() call in the file."""
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        # A file the linter cannot parse is its own failure mode — the
        # test suite will say more; don't mask it as "no prints".
        yield (e.lineno or 0, f"SYNTAX ERROR: {e.msg}")
        return
    lines = source.splitlines()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            text = lines[node.lineno - 1].strip() if node.lineno <= len(
                lines) else ""
            yield (node.lineno, text)


def main(argv) -> int:
    if len(argv) > 1:
        root = argv[1]
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        root = os.path.join(repo, "npairloss_tpu")
    failures = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in sorted(filenames):
            if not name.endswith(".py") or name in EXEMPT_BASENAMES:
                continue
            path = os.path.join(dirpath, name)
            if os.path.relpath(path, root) in EXEMPT_RELPATHS:
                continue
            for lineno, text in find_prints(path):
                failures.append(f"{path}:{lineno}: {text}")
    if failures:
        sys.stderr.write(
            "bare print() in library code (use logging or obs sinks):\n"
        )
        for f in failures:
            sys.stderr.write(f"  {f}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
