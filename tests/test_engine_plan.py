"""``plan_engine`` alone: what an engine will be, decided from the
constructor's arguments with no pool, no lane-state store and no thread.

The six kinds are the dense default and the tiny specs the model tests
build (``tests/test_glm_moe.py``, ``test_jamba.py``, ``test_keye_sparse.py``,
``test_qwen3_next.py``, ``test_evabyte.py``).  One test holds each kind's
plan to what a constructed engine reports; the rest call the plan function
and nothing else: the refusals by their words, the round's budget by the
cases ``tests/test_packed_round.py`` holds an engine to.
"""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_evabyte
import test_glm_moe
import test_jamba
import test_keye_sparse
import test_qwen3_next
from tpulab.engine import kv_pool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import round_width
from tpulab.engine.plan import EnginePlan, plan_engine
from tpulab.models import spec as specs
from tpulab.models.transformer import init_transformer_params
from tpulab.ops import ragged_attention
from tpulab.parallel import make_mesh

#: kind -> (spec, vocab, d_ff for ``init_params``, constructor keywords)
KINDS = {
    "dense": (None, 64, 64, dict(lanes=2, max_len=64, page_size=8)),
    "glm-latent-moe": (
        specs.glm4_moe_lite_spec(test_glm_moe.CONFIG), test_glm_moe.VOCAB,
        test_glm_moe.D_FF, dict(lanes=2, max_len=64, page_size=8)),
    "jamba-mamba": (
        specs.jamba_spec(test_jamba.CONFIG), test_jamba.VOCAB,
        test_jamba.D_FF, dict(lanes=3, max_len=64, page_size=8,
                              prefill_chunk=8)),
    "keye-indexer": (
        specs.keye_vl2_spec(test_keye_sparse.CONFIG), test_keye_sparse.VOCAB,
        0, dict(lanes=3, max_len=64, page_size=8, prefill_chunk=8)),
    "qwen3next-gdn": (
        specs.qwen3_next_spec(test_qwen3_next.CONFIG), test_qwen3_next.VOCAB,
        0, dict(lanes=3, max_len=64, page_size=8, prefill_chunk=8)),
    "evabyte-eva": (
        specs.evabyte_spec(test_evabyte.CONFIG), test_evabyte.VOCAB,
        test_evabyte.D_FF, dict(lanes=3, max_len=160, page_size=4,
                                prefill_chunk=12)),
}


def _plan(spec=None, **kw):
    """``plan_engine`` with the constructor's defaults where ``kw`` is
    silent: the dense test model's heads, layers and width, or a spec's."""
    shape = (dict(n_heads=2, n_layers=2, d_model=32) if spec is None else
             dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
                  d_model=spec.d_model))
    return plan_engine(spec=spec, **{**dict(
        n_kv_heads=None, rope_theta=None, vocab=64, lanes=4, max_len=256,
        page_size=16, prefill_chunk=None, use_kernel=None,
        compute_dtype=jnp.float32, kv_dtype=None,
        round_ceiling=ContinuousBatcher.RAGGED_CHUNK_CAP,
        kernel_auto_min_ctx=ContinuousBatcher.KERNEL_AUTO_MIN_CTX),
        **shape, **kw})


@pytest.fixture
def nothing_allocated(monkeypatch):
    """Fails the test that builds a pool or a lane-state store, puts
    anything on a device or starts a thread."""
    def refuse(*a, **k):
        raise AssertionError("plan_engine allocates nothing")
    monkeypatch.setattr(kv_pool.PagedKVPool, "__init__", refuse)
    monkeypatch.setattr(kv_pool.LaneStateStore, "__init__", refuse)
    monkeypatch.setattr(jax, "device_put", refuse)
    monkeypatch.setattr(threading.Thread, "start", refuse)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_plan_is_what_a_constructed_engine_reports(kind, use_kernel):
    """The plan of each kind of model, made from the arguments alone, and
    the engine those arguments build: the attention's form, the page tables'
    width, the round's budget, and a page store of the layers, entry kind
    and bytes a token the plan's fields give."""
    spec, vocab, d_ff, kw = KINDS[kind]
    kw = dict(kw, use_kernel=use_kernel)
    if spec is None:
        params = init_transformer_params(vocab=vocab, d_model=32, n_heads=2,
                                         n_layers=2, d_ff=d_ff)
        heads, layers = 2, 2
    else:
        params = specs.init_params(spec, vocab, d_ff)
        heads, layers = spec.n_heads, spec.n_layers
    plan = _plan(spec, vocab=vocab, **kw)
    cb = ContinuousBatcher(params, heads, layers, spec=spec,
                           compute_dtype=jnp.float32, **kw)
    try:
        pool = cb.debug_state()["pool"]
        state = cb.debug_state().get("state")
        tile = cb.debug_state()["dispatch"]["latent_tile"]
    finally:
        cb.shutdown()
    assert isinstance(plan, EnginePlan) and cb.plan == plan
    # the latent kernels' tile, where they run: every head of a lane at
    # one row, and at the round's width what the kernel's plan gives
    assert tile == plan.latent_tile
    assert (tile is not None) == bool(plan.latent and use_kernel)
    if tile:
        assert tile["one_row"]["heads"] == heads
        assert tile["one_row"]["walks"] == 1
        assert tile["round"]["heads"] * tile["round"]["walks"] == heads
    assert (cb.max_pages, cb.RAGGED_CHUNK_CAP, cb.use_kernel,
            cb.round_budget_why, cb.prefill_chunk, cb.vocab) == (
        plan.max_pages, plan.round_cap, plan.use_kernel,
        plan.round_budget_why, plan.prefill_chunk, vocab)
    assert plan.use_kernel == use_kernel
    assert cb.pool.n_layers == plan.pool_layers
    assert pool["n_pages"] == plan.max_pages * plan.lanes + 1
    assert pool["entry_kind"] == ("latent" if plan.latent else
                                  "kv_index" if plan.sparse else "kv")
    row = lambda n: -(-n // 128) * 128      # noqa: E731
    index = plan.pool_layers * row(plan.index_dim) * 4 if plan.sparse else 0
    assert pool["index_bytes_per_token"] == index
    assert pool["bytes_per_token"] == index + plan.pool_layers * 4 * (
        row(plan.latent) if plan.latent else 2 * plan.n_kv * plan.head_dim)
    assert (state and state["kind"]) == plan.state_kind
    assert "spec" not in plan.step_kw if spec is None else (
        plan.step_kw["spec"] is spec)


REFUSED = [
    ("draft_params", dict(draft_params={"layer0": {}})),
    ("mesh", dict(mesh=object())),
    ("kv_offload", dict(kv_offload=True)),
    ("kv_publish", dict(kv_publish=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_dtype other than the compute dtype",
     dict(kv_dtype=jnp.float8_e4m3fn)),
    ("hbm", dict(hbm=object())),
]


@pytest.mark.parametrize("name,option", REFUSED, ids=[n for n, _ in REFUSED])
def test_each_refused_option_is_refused_by_name(nothing_allocated, name,
                                                option):
    """The seven options another cache entry or a lane state does not
    carry, each refused for a kind of model in the words the
    constructor raised, and only the one that is set."""
    spec = KINDS["jamba-mamba"][0]
    with pytest.raises(NotImplementedError) as e:
        _plan(spec, **option)
    said = str(e.value)
    assert said.startswith("a model with kv pages, mamba layers is not "
                           "supported with: ")
    assert [n for n, _ in REFUSED if n in said.partition(": ")[2]] == [name]
    # a dense model takes each of them (a mesh and an arbiter: not both)
    if name not in ("mesh", "draft_params"):
        assert _plan(**option).state_kind is None


@pytest.mark.parametrize("name", ["ragged", "prefill_flash", "verify_width"])
def test_the_plan_takes_no_option_that_chose_a_plan(nothing_allocated, name):
    """There is one dispatch plan: neither ``plan_engine`` nor the
    scheduler's constructor takes ``ragged`` or ``prefill_flash`` (nor the
    plan the K + 1 verify width the other plan's geometry check read), and
    ``EnginePlan`` has no such field."""
    import dataclasses
    with pytest.raises(TypeError, match=name):
        _plan(**{name: None})
    with pytest.raises(TypeError, match=name):
        ContinuousBatcher({}, 2, 2, **{name: None})
    assert name not in {f.name for f in dataclasses.fields(EnginePlan)}


def _pool(**facts):
    """What ``plan_engine`` reads of a provided pool."""
    return types.SimpleNamespace(**dict(dict(
        dtype=jnp.float32, entry_kind="kv", n_layers=2, index=None,
        mesh=None), **facts))


def _draft(d_model):
    """What ``plan_engine`` reads of a draft's parameters."""
    return {"layer0": {"wqkv": np.zeros((d_model, 3 * d_model))}}


def _mesh(first=0):
    return make_mesh({"model": 2}, jax.devices()[first:first + 2])


ERRORS = {
    "spec-against-heads": (
        lambda: _plan(KINDS["jamba-mamba"][0], n_heads=2),
        ValueError, r"spec \(n_heads 4, n_layers 5\) disagrees with "
                    "n_heads=2, n_layers=5"),
    "page-against-eva-chunk": (
        lambda: _plan(KINDS["evabyte-eva"][0], page_size=8, max_len=160),
        ValueError, "page_size 8 is not the spec's eva_chunk 4: a chunk's "
                    "summary is taken from one page"),
    "chunk-under-a-page": (
        lambda: _plan(prefill_chunk=8),
        ValueError, "prefill_chunk must be >= page_size"),
    "pool-of-another-dtype": (
        lambda: _plan(kv_dtype=jnp.float8_e4m3fn, pool=_pool()),
        ValueError, "kv_dtype=float8_e4m3fn conflicts with the provided "
                    "pool's dtype float32"),
    "pool-of-another-kind": (
        lambda: _plan(KINDS["glm-latent-moe"][0], pool=_pool(n_layers=3)),
        ValueError, "the provided pool holds 'kv' entries, the model "
                    "another kind"),
    "pool-of-other-layers": (
        lambda: _plan(KINDS["jamba-mamba"][0], pool=_pool(n_layers=5)),
        ValueError, "the provided pool has 5 layers, the model 2 attention "
                    "layers"),
    "pool-without-index-rows": (
        lambda: _plan(KINDS["keye-indexer"][0], pool=_pool()),
        ValueError, "the model has an indexer: the provided pool needs "
                    r"index rows \(index_dim=\)"),
    "pool-on-another-mesh": (
        lambda: _plan(mesh=_mesh(), pool=_pool(mesh=_mesh(2))),
        ValueError, "provided pool was built on a different mesh"),
    "arbiter-under-a-mesh": (
        lambda: _plan(mesh=_mesh(), hbm=object()),
        NotImplementedError, "HBM-arbiter-armed serving .* under a mesh is "
                             "not supported"),
    "heads-that-do-not-divide-the-shards": (
        lambda: _plan(n_heads=3, d_model=48, mesh=_mesh(), use_kernel=True),
        ValueError, r"use_kernel under a mesh needs query heads \(3\) "
                    r"divisible by the model axis \(2\)"),
    "draft-of-another-geometry": (
        lambda: _plan(draft_params=_draft(64)),
        ValueError, "draft model KV geometry .* must match the target's"),
    "draft-deeper-than-the-target": (
        lambda: _plan(draft_params=_draft(32), draft_n_layers=3),
        ValueError, "draft_n_layers must be <= n_layers"),
}


@pytest.mark.parametrize("case", list(ERRORS))
def test_each_refused_geometry_raises_in_the_constructors_words(
        nothing_allocated, case):
    call, kind, words = ERRORS[case]
    with pytest.raises(kind, match=words):
        call()


def _geometry_plan(**kw):
    """``tests/test_packed_round.py``'s ``_geometry_engine``: two heads of
    64 on pages of 8 rows, the smallest geometry the rows kernel's rule
    admits."""
    return _plan(**{**dict(d_model=128, n_layers=1, lanes=2, max_len=1024,
                           page_size=8, use_kernel=True), **kw})


@pytest.mark.parametrize("admits,kw,budget", [
    (512, {}, 512), (256, {}, 256), (64, {}, 64),
    (256, dict(prefill_chunk=96), 96), (64, dict(prefill_chunk=16), 16)],
    ids=["admits-512", "refuses-512", "refuses-128", "chunk-under-the-cap",
         "chunk-fits-where-the-cap-would-not"])
def test_the_budget_is_the_widest_round_the_geometry_rule_admits(
        monkeypatch, nothing_allocated, admits, kw, budget):
    """The plan asks the kernels' rule, the ceiling first and then each
    power of two under it, and keeps what refused the next wider round;
    ``prefill_chunk`` is what the rule is asked about."""
    monkeypatch.setattr(
        ragged_attention, "_VMEM_REQUEST_MAX", ragged_attention._plan(
            admits, 2, 2, 64, 8, 128, jnp.float32, jnp.float32)[2])
    plan = _geometry_plan(**kw)
    chunk = kw.get("prefill_chunk")
    cap = 512 if chunk and round_width(chunk) <= admits else admits
    assert (plan.round_cap, plan.use_kernel) == (cap, True)
    assert min(plan.prefill_chunk or cap, cap) == budget
    if cap == 512:
        assert plan.round_budget_why is None
    else:
        assert f"q_len={2 * cap}" in plan.round_budget_why
        assert "VMEM" in plan.round_budget_why


@pytest.mark.parametrize("max_len,window,budget", [
    (1024, 0, 512), (1023, 0, 256), (640, 0, 256), (256, 0, 128), (24, 0, 8),
    (160, 32, 32)])
def test_twice_the_budget_fits_max_len(nothing_allocated, max_len, window,
                                       budget):
    """The widest power of two ``max_len`` holds twice (each width's
    program is reached by one prompt that spends the budget and leaves a
    tail), and with EVA windows at most a window, where a chunk ends."""
    plan = (_plan(KINDS["evabyte-eva"][0], max_len=max_len, page_size=4)
            if window else _plan(max_len=max_len, use_kernel=False))
    assert plan.round_cap == budget and plan.eva_window == window
    assert (plan.round_budget_why is None) == (budget == 512)
    if budget < 512:
        assert f"max_len {max_len}" in plan.round_budget_why
        assert ("windows of 32" in plan.round_budget_why) == bool(window)


def test_a_geometry_that_admits_no_round_is_refused_before_anything_is_built(
        monkeypatch, nothing_allocated):
    """Where the rule refuses every width nothing is narrowed: the
    interpreter, which the rule does not bind, plans the ceiling; a compiled
    engine is refused in the rule's words; ``use_kernel=None`` on a chip
    plans the gather."""
    from tpulab.tpu import platform
    monkeypatch.setattr(ragged_attention, "_VMEM_REQUEST_MAX", 1 << 10)
    plan = _geometry_plan()
    assert (plan.round_cap, plan.round_budget_why) == (512, None)
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    with pytest.raises(ValueError, match="use_kernel=True: kernel VMEM .* "
                                         "for one row a lane"):
        _geometry_plan()
    monkeypatch.setattr(platform, "is_tpu", lambda: True)
    auto = _geometry_plan(use_kernel=None, max_len=8192)
    assert auto.use_kernel is False


def test_a_width_the_rule_refuses_keeps_the_xla_rule_in_both_programs(
        monkeypatch, nothing_allocated):
    """The tiny Gated DeltaNet model's heads are 16 wide, not whole 128-lane
    tiles: on a chip the automatic plan takes no kernel, so the decode step
    and the round both run the XLA rule, and a kernel asked for by name is
    refused in ``rule_geometry_error``'s words.  The interpreter, which the
    rule does not bind, runs the kernels in both; the Mamba hybrid's decode
    step is XLA in either plan; a model without a lane state has no rule."""
    from tpulab.tpu import platform
    spec = KINDS["qwen3next-gdn"][0]
    kernels = dict(decode="kernel", round="kernel")
    assert _plan(spec, use_kernel=True).state_rule == kernels
    assert _plan(spec, use_kernel=False).state_rule == dict(
        decode="xla", round="xla")
    assert _plan(KINDS["jamba-mamba"][0], use_kernel=True).state_rule == dict(
        decode="xla", round="kernel")
    assert _plan(use_kernel=True).state_rule is None
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    monkeypatch.setattr(platform, "is_tpu", lambda: True)
    auto = _plan(spec, use_kernel=None, max_len=8192)
    assert (auto.use_kernel, auto.state_rule) == (
        False, dict(decode="xla", round="xla"))
    with pytest.raises(ValueError, match="use_kernel=True: head widths d_k "
                                         "16, d_v 16 are not whole 128-lane"):
        _plan(spec, use_kernel=True)
    wide = specs.qwen3_next_spec(dict(      # and pages of whole 128-lane rows
        test_qwen3_next.CONFIG, linear_key_head_dim=128,
        linear_value_head_dim=128, head_dim=64))
    assert _plan(wide, use_kernel=None, max_len=8192).state_rule == kernels


#: the two latent configurations' kernel calls: heads, pages a lane, and the
#: heads a tile the plan gives a chunk of 512 rows (a row of 640, 512 of
#: them the value, pages of 16, bf16)
LATENT_CALLS = {"longcat-flash": (64, 1024, 8), "glm47flash": (20, 512, 5)}


@pytest.mark.parametrize("config", list(LATENT_CALLS))
def test_the_latent_tile_of_a_chunk_fits_vmem_and_one_row_keeps_its_tile(
        config):
    """At the published widths a chunk lane's tile is several heads within
    the tile budget, so a lane's pages are walked ``heads / tile`` times
    and not once a head; one row a lane is every head one tile, as it
    was."""
    heads, max_pages, heads_tile = LATENT_CALLS[config]
    shape = (heads, 640, 512, 16, max_pages, jnp.bfloat16, jnp.bfloat16)
    plan = ragged_attention._latent_plan(512, *shape)
    assert (plan.heads_tile, plan.rows) == (heads_tile, 512 * heads_tile)
    assert plan.vmem_bytes <= ragged_attention._LATENT_TILE_BUDGET
    assert ragged_attention.latent_geometry_error(512, *shape) is None
    assert ragged_attention.latent_tile(512, *shape) == dict(
        heads=heads_tile, walks=heads // heads_tile,
        vmem_bytes=plan.vmem_bytes)
    one = ragged_attention._latent_plan(1, *shape)
    assert (one.heads_tile, one.rows) == (heads, -(-heads // 16) * 16)
    assert ragged_attention.latent_geometry_error(1, *shape) is None
    # the tile follows the rows: a narrower chunk's holds more heads
    assert [ragged_attention._latent_plan(m, *shape).heads_tile
            for m in (256, 64, 16)] == ([16, 64, 64] if heads == 64
                                        else [10, 20, 20])


def test_a_latent_tile_past_vmem_is_refused_with_the_widest_that_fits():
    shape = (64, 640, 512, 16, 1024, jnp.bfloat16, jnp.bfloat16)
    assert ragged_attention._latent_plan(
        512, *shape, heads_tile=64).vmem_bytes > \
        ragged_attention._VMEM_REQUEST_MAX
    said = ragged_attention.latent_geometry_error(512, *shape, heads_tile=64)
    assert "at 64 heads a tile exceeds the 96 MiB" in said
    assert "the widest tile that fits holds 8" in said
    assert ragged_attention.latent_geometry_error(
        512, *shape, heads_tile=8) is None
    # a segment no tile holds: the plan falls to one head and says so
    said = ragged_attention.latent_geometry_error(16384, *shape)
    assert "at 1 heads a tile" in said and "no tile fits" in said
