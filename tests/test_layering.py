"""Imports point down: a module under ``src/repro`` imports only its own
package and packages of a lower rank, deferred (function-level) imports
included.  The rank table, bottom up:

    configs, runtime, optim, checkpoint  <  data, core  <  kernels, models
    <  engine  <  faults  <  analysis  <  launch

Packages that share a rank do not import each other.  A package directory
missing from the table fails its own case until it is given a rank.
"""
import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"

RANKS = (
    ("configs", "runtime", "optim", "checkpoint"),
    ("data", "core"),
    ("kernels", "models"),
    ("engine",),
    ("faults",),
    ("analysis",),
    ("launch",),
)
RANK = {pkg: rank for rank, pkgs in enumerate(RANKS) for pkg in pkgs}
PACKAGES = sorted(set(RANK) | {d.name for d in SRC.iterdir()
                               if d.is_dir() and d.name != "__pycache__"})


def _imported(path: pathlib.Path):
    """(line, package) for every ``repro.<package>`` the module imports,
    at any depth of its syntax tree."""
    parts = ("repro",) + path.relative_to(SRC).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = parts[:len(parts) - node.level + 1]
                module = ".".join(base + ((node.module,) if node.module
                                          else ()))
            else:
                module = node.module or ""
            names = ([f"repro.{alias.name}" for alias in node.names]
                     if module == "repro" else [module])
        else:
            continue
        for name in names:
            dotted = name.split(".")
            if dotted[0] == "repro" and len(dotted) > 1:
                yield node.lineno, dotted[1]


@pytest.mark.parametrize("package", PACKAGES)
def test_imports_point_down(package):
    assert package in RANK, f"repro.{package} has no rank in the table"
    upward = []
    for path in sorted((SRC / package).rglob("*.py")):
        for line, target in _imported(path):
            if target != package and RANK.get(target, len(RANKS)) \
                    >= RANK[package]:
                upward.append(f"{path.relative_to(SRC)}:{line} imports "
                              f"repro.{target}")
    assert not upward, "imports that point up:\n  " + "\n  ".join(upward)
