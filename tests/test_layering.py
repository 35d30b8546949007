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


def test_the_schedulers_constructor_keeps_its_30_parameters():
    """What ``perf/models/*.py``, ``rpc/`` and ``fleet/`` call: the names,
    their order and their defaults.  There is one dispatch plan and no
    flash prefill: ``ragged`` and ``prefill_flash`` are not among them."""
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
        trace=None, metrics=None, decode_block=8,
        kv_offload=None, draft_params=None, draft_n_layers=None,
        draft_n_heads=None, draft_n_kv_heads=None, spec_accept_floor=0.35,
        mesh=None, hbm=None, flight=None, kv_publish=False, spec=None)
    assert len(want) == 30
    assert [(p.name, p.default) for p in params] == list(want.items())


@pytest.fixture(scope="module")
def benchmark_engine():
    """A dense engine at the constructor's defaults but its size: what
    ``perf/models/*.py`` and ``perf/layer_metrics/*.py`` read is read of
    it, before it has served a request."""
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    cb = ContinuousBatcher(params, 2, 2, lanes=2, max_len=64, page_size=8,
                           compute_dtype=jnp.float32)
    yield cb
    cb.shutdown()


#: what ``perf/models/*.py`` read of a ``ContinuousBatcher`` (``grep -rn
#: "cb\." perf/models perf/layer_metrics perf/harness``): the seam no PR but
#: a ``benchmark`` one may edit from the other side, held from this one
BENCHMARK_ATTRIBUTES = [
    "ragged", "use_kernel", "prefill_flash", "prefill_chunk", "decode_block",
    "lanes", "max_len", "max_pages", "page_size", "RAGGED_CHUNK_CAP",
    "pool.n_pages", "pool.hbm_bytes", "pool.free_pages",
    "pool.bytes_per_token", "pool.n_layers", "pool.entry_kind", "pool.kv",
    "plan.walk_block_pages", "active_lanes", "queued_requests",
    "decode_holdings", "decode_window_pages", "debug_state", "shutdown",
    # None on this dense engine: a lane state's store (``.kind``, ``.arrays``,
    # ``.bytes_per_lane``, ``.hbm_bytes``), the window layers' page group
    "state", "wpool"]

#: the keys ``perf/layer_metrics/*.py`` and ``perf/models/*.py`` subscript
#: without ``.get`` in ``debug_state()["dispatch"]``
BENCHMARK_DISPATCH_KEYS = [
    "tokens_generated", "decode_dispatches", "prefill_dispatches",
    "decode_host_syncs", "preemptions", "kinds", "lane_work", "round_budget",
    "mixed_decode_rows"]


@pytest.mark.parametrize("name", BENCHMARK_ATTRIBUTES)
def test_the_engine_keeps_each_attribute_the_benchmark_reads(
        benchmark_engine, name):
    value = benchmark_engine
    for part in name.split("."):
        value = getattr(value, part)      # AttributeError names the part
    if name in ("ragged", "prefill_flash"):
        # a choice the engine no longer has, under the name the benchmark
        # prints it by: the one plan, no flash prefill
        assert value is (name == "ragged")


@pytest.mark.parametrize("key", BENCHMARK_DISPATCH_KEYS)
def test_debug_state_keeps_each_dispatch_key_the_benchmark_subscripts(
        benchmark_engine, key):
    dispatch = benchmark_engine.debug_state()["dispatch"]
    assert key in dispatch
    if key == "prefill_dispatches":
        assert dispatch[key] == 0 and dispatch["ragged"] is True
    if key == "kinds":
        assert set(dispatch[key]) == {"decode", "verify", "mixed"}
    if key == "lane_work":
        assert {"passes", "rows", "keys"} <= set(dispatch[key]["decode"])
