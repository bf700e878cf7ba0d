"""The package stands on nothing above it.

``perfbench/``, ``tests/`` and ``scripts/`` import ``bagua_tpu``; no module
of ``bagua_tpu`` imports any of them (or the ``bench`` / ``benchmarks``
yardstick that PR 46 deleted), at module level or inside a function.
"""

import ast
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "bagua_tpu"
ABOVE = {"bench", "benchmarks", "perfbench", "tests", "scripts"}
SUBPACKAGES = sorted(p.name for p in PACKAGE.iterdir()
                     if (p / "__init__.py").exists())


def _imports_from_above(path: pathlib.Path):
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module]
        else:
            continue
        for name in names:
            if name.split(".")[0] in ABOVE:
                where = path.relative_to(PACKAGE.parent)
                yield f"{where}:{node.lineno}: {name}"


@pytest.mark.parametrize("part", SUBPACKAGES + ["top_level_modules"])
def test_package_imports_nothing_above_it(part):
    files = (sorted(PACKAGE.glob("*.py")) if part == "top_level_modules"
             else sorted((PACKAGE / part).rglob("*.py")))
    assert files, part
    found = [hit for f in files for hit in _imports_from_above(f)]
    assert not found, "\n".join(found)
