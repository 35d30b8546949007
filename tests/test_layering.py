"""The arrows between the scheduler and what lies under it point one way.

``tpulab/engine/paged.py`` is the scheduler (``ContinuousBatcher``).  The
packages it is built on, and the three modules split out of it, never import
it: a page helper, a sampler or a plan is reached where it lives
(``engine/kv_pool.py``, ``engine/paged_steps.py``, ``engine/plan.py``), and
the scheduler neither jits a program nor asks a kernel's geometry rule.
Imports are read with ``ast`` from every file of a package, at any depth of
a function body; docstrings and comments do not count.
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
    plan = ROOT / "engine" / "plan.py"
    assert _importers_of(SCHEDULER, [kv_pool, steps, plan]) == []
    assert _importers_of("tpulab.engine.paged_steps", [kv_pool]) == []
    assert _importers_of("tpulab.engine.plan", [kv_pool, steps]) == []
    # the arrows that do exist (so the reader above is shown to see one):
    # the steps write rows into the page store, the plan asks a round's width
    assert _importers_of("tpulab.engine.kv_pool", [steps]) != []
    assert _importers_of("tpulab.engine.paged_steps", [plan]) != []


def test_the_scheduler_builds_no_program_and_asks_no_kernel():
    """``engine/paged.py`` schedules: the geometry rules of ``tpulab.ops``
    are ``engine/plan.py``'s to ask, the step functions and ``jax.jit``
    ``StepPrograms``' (``engine/paged_steps.py``), with the memo."""
    path = ROOT / "engine" / "paged.py"
    imports = set(_imports(path))
    assert not {m for m in imports if m.startswith("tpulab.ops")}
    assert not {m for m in imports
                if m.startswith("tpulab.engine.paged_steps.paged_")}
    assert "tpulab.engine.paged_steps.StepPrograms" in imports
    assert "tpulab.engine.plan.plan_engine" in imports
    tree = ast.parse(path.read_text(), str(path))
    jits = [node.lineno for node in ast.walk(tree)
            if isinstance(node, ast.Attribute) and node.attr == "jit"]
    assert jits == []
    defined = {node.name for node in ast.walk(tree)
               if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
    defined |= {node.id for node in ast.walk(tree)
                if isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Store)}
    assert not defined & {"_JIT_MEMO", "_jit", "_build_prefill", "_block_fn",
                          "_spec_block_fn"}


def test_the_schedulers_constructor_keeps_its_32_parameters():
    """What ``perf/models/*.py``, ``rpc/`` and ``fleet/`` call: the names,
    their order and their defaults."""
    import inspect

    from tpulab.engine.paged import ContinuousBatcher
    params = list(inspect.signature(
        ContinuousBatcher.__init__).parameters.values())[1:]
    empty = inspect.Parameter.empty
    want = dict(
        params=empty, n_heads=empty, n_layers=empty, pool=None, lanes=4,
        max_len=256, page_size=16, n_pages=0, compute_dtype=None,
        device=None, use_kernel=None, n_kv_heads=None, rope_theta=None,
        prefix_cache=False, prefill_chunk=None, kv_dtype=None,
        prefill_flash=None, trace=None, metrics=None, decode_block=8,
        kv_offload=None, draft_params=None, draft_n_layers=None,
        draft_n_heads=None, draft_n_kv_heads=None, spec_accept_floor=0.35,
        mesh=None, hbm=None, flight=None, ragged=None, kv_publish=False,
        spec=None)
    assert len(want) == 32
    assert [(p.name, p.default) for p in params] == list(want.items())
