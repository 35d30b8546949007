"""The arrows between the scheduler and what lies under it point one way.

``tpulab/engine/paged.py`` is the scheduler (``ContinuousBatcher``).  The
packages it is built on, and the two modules split out of it, never import
it: a page helper or a sampler is reached where it lives
(``engine/kv_pool.py``, ``engine/paged_steps.py``).  Imports are read with
``ast`` from every file of a package, at any depth of a function body;
docstrings and comments do not count.
"""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "tpulab"
SCHEDULER = "tpulab.engine.paged"


def _imports(path: pathlib.Path):
    """Every module a file imports, absolute: ``import a.b`` gives
    ``a.b``; ``from a import b`` gives ``a`` and ``a.b`` (``b`` may be a
    module); a relative import is resolved against the file's package."""
    package = ("tpulab",) + path.relative_to(ROOT).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                above = package[:len(package) - (node.level - 1)]
                base = ".".join(above + ((base,) if base else ()))
            yield base
            for alias in node.names:
                yield f"{base}.{alias.name}"


def _importers_of(module: str, files):
    return sorted(str(f.relative_to(ROOT.parent)) for f in files
                  if module in set(_imports(f)))


@pytest.mark.parametrize("package", [
    "ops", "parallel", "models", "kvcache", "hbm", "modelstore", "disagg",
    "obs", "kvfabric"])
def test_package_under_the_scheduler_does_not_import_it(package):
    files = sorted((ROOT / package).rglob("*.py"))
    assert files, package
    assert _importers_of(SCHEDULER, files) == []


def test_split_modules_do_not_import_the_scheduler():
    kv_pool = ROOT / "engine" / "kv_pool.py"
    steps = ROOT / "engine" / "paged_steps.py"
    assert _importers_of(SCHEDULER, [kv_pool, steps]) == []
    assert _importers_of("tpulab.engine.paged_steps", [kv_pool]) == []
    # the arrow that does exist (so the reader above is shown to see one):
    # the steps write rows into the page store
    assert _importers_of("tpulab.engine.kv_pool", [steps]) != []
