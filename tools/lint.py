#!/usr/bin/env python
"""Self-contained linter (the .golangci.yml analog for an image with no
ruff/flake8 installed; pyproject.toml carries the ruff config for
environments that have it).

Checks, in the spirit of the reference's errcheck/govet/unused set:
  syntax        every file parses (ast)
  unused-import module-level imports never referenced
  tabs          no tab indentation
  trailing-ws   no trailing whitespace
  long-lines    > 100 columns (warn only)
  bare-except   `except:` without an exception class
  debug-print   print() in library code (CLIs/benchmarks exempt)

Exit status 1 on any error-level finding. Usage: python tools/lint.py
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_DIRS = ("ratelimiter_tpu", "tests", "tools")
#: print() is the UI in these (CLI entry points, test harness).
PRINT_OK = {"ratelimiter_tpu/serving/__main__.py",
            "tools", "tests", "__graft_entry__.py"}


def _print_allowed(rel: str) -> bool:
    return any(rel == p or rel.startswith(p.rstrip("/") + "/")
               or rel.startswith(p) for p in PRINT_OK)


class _ImportVisitor(ast.NodeVisitor):
    def __init__(self):
        self.imports: dict[str, int] = {}   # name -> lineno
        self.used: set[str] = set()

    def visit_Import(self, node):
        for a in node.names:
            name = (a.asname or a.name).split(".")[0]
            self.imports[name] = node.lineno

    def visit_ImportFrom(self, node):
        if node.module == "__future__":
            return  # compiler directives, not bindings
        for a in node.names:
            if a.name == "*":
                continue
            self.imports[a.asname or a.name] = node.lineno

    def visit_Name(self, node):
        self.used.add(node.id)

    def visit_Attribute(self, node):
        self.generic_visit(node)


def lint_file(path: str, rel: str) -> list[tuple[str, int, str]]:
    errs: list[tuple[str, int, str]] = []
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [("syntax", e.lineno or 0, str(e.msg))]

    for i, line in enumerate(src.splitlines(), 1):
        if line.rstrip("\n") != line.rstrip():
            errs.append(("trailing-ws", i, "trailing whitespace"))
        if line.startswith("\t"):
            errs.append(("tabs", i, "tab indentation"))

    # Unused module-level imports (conservative: any Name/attr use or
    # __all__ mention counts; noqa comment suppresses).
    lines = src.splitlines()
    v = _ImportVisitor()
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            v.visit(node)
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            v.used.add(node.id)
        elif isinstance(node, ast.Attribute):
            pass
    exported = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    for elt in getattr(node.value, "elts", []):
                        if isinstance(elt, ast.Constant):
                            exported.add(str(elt.value))
    for name, lineno in v.imports.items():
        line = lines[lineno - 1] if lineno - 1 < len(lines) else ""
        if name not in v.used and name not in exported \
                and "noqa" not in line and not name.startswith("_"):
            errs.append(("unused-import", lineno, f"'{name}' imported but unused"))

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            errs.append(("bare-except", node.lineno, "bare 'except:'"))
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "print" and not _print_allowed(rel)):
            errs.append(("debug-print", node.lineno,
                         "print() in library code"))
    return errs


def main() -> int:
    failures = 0
    warnings = 0
    targets = []
    for d in LINT_DIRS:
        root = os.path.join(REPO, d)
        if os.path.isfile(root):
            targets.append(root)
            continue
        for dirpath, _dirnames, filenames in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            targets.extend(os.path.join(dirpath, f)
                           for f in filenames if f.endswith(".py"))
    targets.append(os.path.join(REPO, "__graft_entry__.py"))
    for path in sorted(targets):
        rel = os.path.relpath(path, REPO)
        for kind, lineno, msg in lint_file(path, rel):
            if kind == "long-lines":
                warnings += 1
            else:
                failures += 1
            print(f"{rel}:{lineno}: [{kind}] {msg}")
    # Long lines: warn only (readability, not correctness).
    for path in sorted(targets):
        rel = os.path.relpath(path, REPO)
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                if len(line.rstrip("\n")) > 100:
                    print(f"{rel}:{i}: [long-line] {len(line.rstrip())} cols (warn)")
                    warnings += 1
    if failures:
        print(f"lint: {failures} error(s), {warnings} warning(s)")
        return 1
    print(f"lint: clean ({len(targets)} files, {warnings} warning(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
