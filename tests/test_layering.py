"""The package's import graph, pinned so that it can only shrink.

Each part of ``tpu_syncbn`` (a sub-package; the top-level modules count
as one part, ``top``) may import the other parts the tables below name
and no other: an edge can be removed, and never added without an edit
here. The tables hold today's graph, cycles and all (``ops -> parallel
-> ops``, ``nn -> parallel -> nn``, ``obs <-> parallel``, ``obs <->
runtime``, ``runtime <-> parallel``, ``parallel -> audit -> parallel``,
``data -> obs``, ``utils -> obs``: ROADMAP.md D11). Source is parsed,
nothing is imported.
"""

import ast
import functools
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "tpu_syncbn"
PARTS = ("audit", "data", "models", "nn", "obs", "ops", "parallel",
         "runtime", "serve", "testing", "utils")
HARNESSES = {"bench", "benchmarks", "chipbench", "tests"}

#: imports at module level (class bodies included)
MODULE_LEVEL = {
    "audit": {"obs"},
    "data": {"obs", "runtime"},
    "models": {"nn", "parallel", "top"},
    "nn": {"ops", "parallel", "runtime"},
    "obs": set(),
    "ops": {"parallel"},
    "parallel": {"models", "obs", "runtime", "top"},
    "runtime": {"obs", "top"},
    "serve": {"obs", "parallel", "runtime"},
    "testing": set(),
    "utils": {"obs", "runtime"},
    "top": {"data", "models", "nn", "obs", "ops", "parallel", "runtime",
            "serve", "utils"},
}
#: imports inside a function body
LAZY = {
    "audit": {"nn", "obs", "parallel", "runtime", "serve", "top"},
    "data": {"parallel", "runtime"},
    "models": {"ops"},
    "nn": {"top"},
    "obs": {"parallel", "runtime"},
    "ops": {"top"},
    "parallel": {"audit", "nn", "obs", "ops", "runtime", "top", "utils"},
    "runtime": {"obs", "parallel", "utils"},
    "serve": {"obs", "parallel", "runtime", "top", "utils"},
    "testing": {"serve", "utils"},
    "utils": {"obs", "runtime", "top"},
    "top": set(),
}


def _part_of(module: str) -> str | None:
    bits = module.split(".")
    if bits[0] != PKG:
        return None
    return bits[1] if len(bits) > 1 and bits[1] in PARTS else "top"


@functools.cache
def _sources() -> list:
    """(dotted package of the file, its part, its syntax tree, its text)
    for every module under the package."""
    found = []
    for folder, _, files in os.walk(os.path.join(ROOT, PKG)):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            package = os.path.relpath(folder, ROOT).split(os.sep)
            module = package + ([] if name == "__init__.py" else [name[:-3]])
            with open(os.path.join(folder, name)) as f:
                text = f.read()
            found.append((package, _part_of(".".join(module)),
                          ast.parse(text), text))
    return found


def _imports(tree: ast.AST, package: list[str]):
    """(absolute dotted module, inside a function?) for every import."""
    def walk(node, lazy):
        for child in ast.iter_child_nodes(node):
            inner = lazy or isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
            if isinstance(child, ast.Import):
                for alias in child.names:
                    yield alias.name, inner
            elif isinstance(child, ast.ImportFrom):
                base = (package[:len(package) - child.level + 1]
                        if child.level else [])
                module = ".".join(base + ([child.module] if child.module
                                          else []))
                if module == PKG:  # ``from tpu_syncbn import nn, compat``
                    for alias in child.names:
                        yield f"{PKG}.{alias.name}", inner
                else:
                    yield module, inner
            yield from walk(child, inner)

    yield from walk(tree, False)


def _graph():
    module_level = {part: set() for part in MODULE_LEVEL}
    lazy = {part: set() for part in LAZY}
    for package, part, tree, _ in _sources():
        for module, inner in _imports(tree, package):
            other = _part_of(module)
            if other is not None and other != part:
                (lazy if inner else module_level)[part].add(other)
    return module_level, lazy


@pytest.fixture(scope="module")
def graph():
    return _graph()


@pytest.mark.parametrize("part", PARTS + ("top",))
def test_a_part_imports_no_part_the_table_does_not_name(graph, part):
    module_level, lazy = graph
    assert module_level[part] <= MODULE_LEVEL[part], (
        f"{part} now imports {sorted(module_level[part] - MODULE_LEVEL[part])}"
        " at module level")
    assert lazy[part] <= LAZY[part], (
        f"{part} now imports {sorted(lazy[part] - LAZY[part])} lazily")


def test_the_package_knows_no_harness():
    """No module of the package imports the benchmark, a probe script or
    the tests, nor names the probes' output directory."""
    for package, _, tree, text in _sources():
        where = "/".join(package)
        for module, _ in _imports(tree, package):
            assert module.split(".")[0] not in HARNESSES, (where, module)
        assert "benchmarks/artifacts" not in text, where
