"""EVA attention on a page table that compacts at every window boundary.

A tiny EvaByte (2 layers, 4 heads of 16, windows of 32 positions in chunks
of 4, pages of 4, three prediction heads), held to the benchmark's plain
float32 reference (``perf/reference/evabyte.py``: a full score matrix by the
definition of what a query sees, the summaries recomputed from the whole
sequence, nothing imported from the program) through the scheduler: prefill
in rounds, the compaction of every finished window, decode blocks that stop
at a boundary.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import paged_eva_compact
from tpulab.models.spec import (ModelSpec, evabyte_spec, init_params,
                                split_pred_heads)
from tpulab.ops.eva_summary import (_summarize_kernel, summarize_chunks,
                                    summary_geometry_error)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, D_FF, WINDOW, CHUNK, LANES = 50, 96, 32, 4, 3
CONFIG = {
    "model_type": "evabyte", "attention_class": "eva", "hidden_size": 64,
    "intermediate_size": D_FF, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "num_pred_heads": 3,
    "window_size": WINDOW, "chunk_size": CHUNK, "rms_norm_eps": 1e-5,
    "rope_theta": 1e5, "rope_scaling": None, "attention_bias": False,
    "norm_add_unit_offset": True, "tie_word_embeddings": False,
    "vocab_size": VOCAB,
}
PUBLISHED = dict(
    CONFIG, hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
    num_key_value_heads=32, num_hidden_layers=32, num_pred_heads=8,
    window_size=2048, chunk_size=16, vocab_size=320)
SUMMARIES = WINDOW // CHUNK


def _load(*path):
    spec = importlib.util.spec_from_file_location(
        "evabyte_" + path[-2], os.path.join(ROOT, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("perf", "reference", "evabyte.py")


@pytest.fixture(scope="module")
def model():
    spec = evabyte_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, D_FF, seed=3, scale=0.1)


def _engine(model, use_kernel=False, **kw):
    spec, params = model
    kw = dict(dict(lanes=LANES, max_len=160, page_size=CHUNK,
                   compute_dtype=jnp.float32, use_kernel=use_kernel,
                   prefill_chunk=12), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             **kw)


@pytest.fixture(scope="module", params=[False, True],
                ids=["xla", "kernels-interpret"])
def engine(request, model):
    cb = _engine(model, use_kernel=request.param)
    yield cb
    cb.shutdown()


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


def _errors(reference, model, prompt, tokens, logprobs):
    return reference.compare(model[1], prompt, tokens, logprobs,
                             **reference.hyper_of(CONFIG))


# ------------------------------------------------------------- the spec ----

def test_spec_reads_the_published_keys_and_maps_positions_to_rows():
    spec = evabyte_spec(PUBLISHED)
    assert (spec.n_layers, spec.d_model, spec.n_heads, spec.n_kv_heads,
            spec.head_dim) == (32, 4096, 32, 32, 128)
    assert (spec.eva_window, spec.eva_chunk, spec.eva_summaries,
            spec.pred_heads) == (2048, 16, 128, 8)
    assert (spec.rms_eps, spec.rope_theta) == (1e-5, 1e5)
    assert spec.cache_entry == "kv" and not spec.state_layers
    hash(spec)     # it keys the jit memo
    # position 2,047 is row 2,047; 2,048 sits behind 128 summaries; 32,767
    # behind 1,920 of them: 3,968 rows where a dense cache holds 32,768
    assert [spec.cache_row(p) for p in (0, 2047, 2048, 4095, 4096, 32767)] \
        == [0, 2047, 128, 2175, 256, 15 * 128 + 2047]
    np.testing.assert_array_equal(
        spec.cache_row(np.array([5, 2048, 6000])), [5, 128, 256 + 1904])
    assert spec.cache_rows_peak(32768) == 3968
    assert [spec.cache_rows_peak(n) for n in (0, 100, 2048, 2049, 4100)] == [
        0, 100, 2048, 2048, 128 + 2048]
    # two windows compacted already: what is left of 6,000 positions
    assert spec.cache_rows_peak(6000, done=2) == 256 + 1904
    plain = ModelSpec(n_layers=1, d_model=8, n_heads=2, n_kv_heads=2,
                      head_dim=4)
    assert plain.cache_row(77) == 77 and plain.cache_rows_peak(77) == 77
    assert plain.eva_summaries == 0


@pytest.mark.parametrize("change,message", [
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"chunk_size": 5}, "whole chunks"),
    ({"window_size": 24, "chunk_size": 4}, "whole chunks"),   # 6 % 4
    ({"attention_class": "softmax"}, "attention_class"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"norm_add_unit_offset": False}, "norm_add_unit_offset"),
    ({"num_chunks": 4}, "num_chunks"),
])
def test_spec_refuses_what_it_cannot_carry(change, message):
    with pytest.raises(ValueError, match=message):
        evabyte_spec(dict(CONFIG, **change))


def test_eva_windows_belong_to_plain_attention():
    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                eva_window=32, eva_chunk=4)
    ModelSpec(**base)
    for extra in ({"attn_gate": True}, {"mixers": ("attention", "mamba"),
                                        "d_inner": 8, "d_state": 4,
                                        "d_conv": 4, "dt_rank": 2},
                  {"index_heads": 2, "index_dim": 8, "index_topk": 4}):
        with pytest.raises(ValueError, match="EVA|indexer"):
            ModelSpec(**dict(base, **extra))
    with pytest.raises(ValueError, match="eva_window"):
        ModelSpec(**dict(base, eva_chunk=0))


def test_the_cut_is_depth_alone_and_a_layer_is_202_million_parameters():
    """The benchmark's 8-layer file and the published 32-layer config give
    specs that differ in ``n_layers`` alone; the parameters of one layer,
    counted by the benchmark's roofline file and by the program's own tree,
    are the 202.4 M the cut was reckoned with."""
    with open(os.path.join(ROOT, "perf", "configs", "evabyte-l8.json")) as f:
        cut = json.load(f)
    roofline = _load("perf", "rooflines", "evabyte.py")
    small, full = evabyte_spec(cut), evabyte_spec(PUBLISHED)
    differ = {k for k in small.__dataclass_fields__
              if getattr(small, k) != getattr(full, k)}
    assert differ == {"n_layers", "layer_kinds", "mixers"}
    assert (small.n_layers, full.n_layers) == (8, 32)
    assert set(cut["reduced"]) == {"num_hidden_layers"}
    assert {k for k, v in PUBLISHED.items() if cut[k] != v} == {
        "num_hidden_layers"}
    assert roofline.layer_params(cut) == 202_391_552
    tree = jax.eval_shape(lambda: init_params(small, 320, 11008))
    count = lambda t: sum(int(np.prod(x.shape))             # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(tree["layer0"]) == roofline.layer_params(cut)
    assert count(tree) == roofline.model_params(cut) == 1_630_932_992
    assert roofline.model_params(PUBLISHED) == 6_488_330_240
    assert tree["lm_head"].shape == (4096, 320)
    assert tree["mtp_heads"].shape == (4096, 7 * 320)
    assert roofline.kv_bytes_per_row(cut) == 131_072
    assert roofline.rows_of(cut, 32768) == small.cache_rows_peak(32768)


def test_split_pred_heads_keeps_head_zero_for_generate():
    spec = evabyte_spec(CONFIG)
    head = np.arange(8 * 3 * VOCAB, dtype=np.float32).reshape(8, 3 * VOCAB)
    lm, rest = split_pred_heads(head, spec)
    np.testing.assert_array_equal(lm, head[:, :VOCAB])
    np.testing.assert_array_equal(rest, head[:, VOCAB:])


# ------------------------------------------------------- the summariser ----

def _pool(seed, layers=2, pages=9, heads=4, dim=16, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((layers, pages, 2, CHUNK, heads * dim))
    scorers = rng.standard_normal((2, layers, heads, dim))
    return (jnp.asarray(pool, dtype), jnp.asarray(scorers[0], jnp.float32),
            jnp.asarray(scorers[1], jnp.float32))


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel-interpret"])
def test_summariser_is_the_two_softmax_poolings_by_hand(use_kernel):
    """``k~ = sum_m softmax_m(mu . k_m) k_m`` and ``v~ = sum_m softmax_m(phi
    . k_m) v_m`` (BOTH scored against the keys), in float64 numpy, a head
    and a page at a time."""
    pool, mu, phi = _pool(5)
    pages = jnp.asarray([3, 1, 7, 2], jnp.int32)
    got = np.asarray(summarize_chunks(pool, pages, mu, phi,
                                      use_kernel=use_kernel))
    assert got.shape == (2, 4, 2, 64)
    f64 = lambda a: np.asarray(a, np.float64)               # noqa: E731
    for layer in range(2):
        for i, page in enumerate((3, 1, 7, 2)):
            k = f64(pool[layer, page, 0]).reshape(CHUNK, 4, 16)
            v = f64(pool[layer, page, 1]).reshape(CHUNK, 4, 16)
            for h in range(4):
                a = np.exp(k[:, h] @ f64(mu[layer, h]))
                b = np.exp(k[:, h] @ f64(phi[layer, h]))
                np.testing.assert_allclose(
                    got[layer, i, 0, 16 * h:16 * h + 16],
                    (a / a.sum()) @ k[:, h], rtol=2e-5, atol=2e-6)
                np.testing.assert_allclose(
                    got[layer, i, 1, 16 * h:16 * h + 16],
                    (b / b.sum()) @ v[:, h], rtol=2e-5, atol=2e-6)


def test_summariser_kernel_matches_the_xla_form_in_bf16():
    pool, mu, phi = _pool(6, dtype=jnp.bfloat16)
    pages = jnp.asarray([8, 0, 4], jnp.int32)
    want = summarize_chunks(pool, pages, mu, phi, use_kernel=False)
    got = summarize_chunks(pool, pages, mu, phi, use_kernel=True)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2,
                               atol=1e-2)


def test_compaction_writes_the_summaries_over_the_windows_first_pages(model):
    """A window of 8 pages becomes 2 pages of summaries IN PLACE (the first
    two of the window's own), every layer; no other page moves."""
    spec, params = model
    pool, _, _ = _pool(7, pages=12)
    window = jnp.asarray([5, 2, 9, 3, 11, 1, 6, 8], jnp.int32)
    out = np.asarray(paged_eva_compact(params, pool, window, spec=spec))
    mu = jnp.stack([params[f"layer{i}"]["eva_mu"] for i in range(2)])
    phi = jnp.stack([params[f"layer{i}"]["eva_phi"] for i in range(2)])
    rows = np.asarray(summarize_chunks(pool, window, mu, phi,
                                       use_kernel=False))
    for layer in range(2):
        for c in range(SUMMARIES):
            page, slot = (5, 2)[c // CHUNK], c % CHUNK
            np.testing.assert_array_equal(out[layer, page, :, slot],
                                          rows[layer, c])
    untouched = [p for p in range(12) if p not in (5, 2)]
    np.testing.assert_array_equal(out[:, untouched],
                                  np.asarray(pool)[:, untouched])


def test_summary_geometry_rule():
    assert summary_geometry_error(128, 16, 16) is None
    assert "eva_chunk" in summary_geometry_error(128, 16, 8)
    assert "128" in summary_geometry_error(64, 16, 16)


# ------------------------------------------- the engine and the reference ----

@pytest.mark.parametrize("n", [5, 31, 32, 33, 64, 70, 100])
def test_streams_match_the_reference_across_window_boundaries(
        engine, model, reference, n):
    """Prefill in rounds of at most 12, then 40 greedy tokens in decode
    blocks: prompts that end before, on and after a boundary, up to three
    compactions in rounds and one or two more in decode."""
    prompt = _prompt(n, seed=n)
    free = engine.pool.free_pages
    before = engine.debug_state()["eva"]
    toks, lps = engine.submit(prompt, steps=40, logprobs=True).result(
        timeout=600)
    got = _errors(reference, model, prompt, toks, lps)
    assert got["logprob_err_max"] < 2e-5 and got["argmax_gap"] == 0.0, got
    after = engine.debug_state()["eva"]
    # the last token is emitted, not taken in
    windows = (n + 40 - 1) // WINDOW
    in_rounds = n // WINDOW
    assert after["compactions"]["round"] - before["compactions"]["round"] \
        == in_rounds
    assert after["compactions"]["decode"] - before["compactions"]["decode"] \
        == windows - in_rounds
    assert after["rows_compacted"] - before["rows_compacted"] \
        == windows * WINDOW
    assert engine.pool.free_pages == free       # every page came home


@pytest.mark.parametrize("offset", range(1, 9))
def test_a_decode_block_started_at_every_offset_before_a_boundary(
        engine, model, reference, offset):
    """The first block of 8 starts ``offset`` positions short of the window's
    end: the lane stops at the boundary inside the block (the device masks
    it), is compacted, and goes on."""
    prompt = _prompt(WINDOW - offset, seed=100 + offset)
    breaks = engine.debug_state()["dispatch"]["chain"]["breaks"]["compact"]
    toks, lps = engine.submit(prompt, steps=20, logprobs=True).result(
        timeout=600)
    assert len(toks) == 20
    got = _errors(reference, model, prompt, toks, lps)
    assert got["logprob_err_max"] < 2e-5 and got["argmax_gap"] == 0.0, got
    d = engine.debug_state()
    assert d["dispatch"]["chain"]["breaks"]["compact"] > breaks
    assert d["eva"]["summary_rows_live"] == d["eva"]["raw_rows_live"] == 0


def test_a_round_where_lanes_finish_windows_while_others_decode(
        engine, model, reference):
    """One stream decodes across a boundary in the rounds that carry
    another's prompt across four; a third joins late.  Every stream is the
    reference's."""
    work = engine.debug_state()["dispatch"]["lane_work"]
    before = engine.debug_state()["eva"]["compactions"]
    jobs = [(_prompt(20, 7), 30), (_prompt(150, 8), 8), (_prompt(61, 9), 12)]
    futures = [engine.submit(p, steps=s, logprobs=True) for p, s in jobs]
    for (prompt, steps), f in zip(jobs, futures):
        toks, lps = f.result(timeout=600)
        assert len(toks) == steps
        got = _errors(reference, model, prompt, toks, lps)
        assert got["logprob_err_max"] < 2e-5 and got["argmax_gap"] == 0.0, got
    after = engine.debug_state()["eva"]["compactions"]
    assert after["round"] - before["round"] == 150 // WINDOW + 61 // WINDOW
    assert after["decode"] - before["decode"] == 1 + 1
    now = engine.debug_state()["dispatch"]["lane_work"]
    # the decode rows of the first stream past position 32 attended 8
    # summaries each; its rows before it none
    assert now["decode"]["summary_keys"] > work["decode"]["summary_keys"]
    assert now["round"]["summary_keys"] > work["round"]["summary_keys"]
    assert 0 < (now["decode"]["summary_keys"] - work["decode"]["summary_keys"]
                ) < now["decode"]["keys"] - work["decode"]["keys"]


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_a_chunk_ends_at_its_window_under_the_widest_budget(model, reference,
                                                            use_kernel):
    """Without ``prefill_chunk`` the budget is the widest a window holds
    (32 here; 512 of EvaByte's 2,048).  Two lanes share it, the older
    first, and the younger one's chunks start off the budget's grid: its
    second chunk ends where its window does, with budget to spare, and
    the streams are the reference's."""
    from tpulab.engine.paged_steps import unpack_words
    cb = _engine(model, use_kernel=use_kernel, prefill_chunk=None, lanes=2)
    state = cb.debug_state()["dispatch"]
    assert state["round_budget"] == WINDOW
    assert f"windows of {WINDOW}" in state["round_budget_why"]
    taken, mixed = [], cb.programs.mixed

    def spy(params, kv, packed, carry):
        q = unpack_words(cb.programs.fields["round"],
                         np.asarray(packed))["q_lens"]
        taken.append([int(q[lane]) if req is not None and req.pf_started
                      else 0 for lane, req in enumerate(cb._active)])
        return mixed(params, kv, packed, carry)
    cb.programs.mixed = spy
    jobs = [(_prompt(20, 21), 6), (_prompt(100, 22), 6)]
    try:
        with cb._cv:     # one admission pass sees both
            futures = [cb.submit(p, steps=s, logprobs=True) for p, s in jobs]
        for (prompt, _steps), f in zip(jobs, futures):
            toks, lps = f.result(timeout=600)
            got = _errors(reference, model, prompt, toks, lps)
            assert got["logprob_err_max"] < 2e-5, got
            assert got["argmax_gap"] == 0.0, got
        state = cb.debug_state()
    finally:
        cb.shutdown()
    chunks = [[n for n in lane if n] for lane in zip(*taken)]
    assert chunks == [[20], [12, 20, 32, 32, 4]]
    assert state["eva"]["compactions"]["round"] == 3
    assert state["dispatch"]["mixed_prompt_tokens"] == 120
    # the first round (20 + 12) and the two whole windows
    assert state["dispatch"]["budget_rounds"] == 3


def test_lane_work_counts_rows_attended_and_the_summaries_among_them(model):
    """A prompt of 40 in chunks of 12 that stop at the boundary: segments
    end at rows 12, 24, 32 and, behind 8 summaries, 8 + 8; ten decode steps
    at positions 40 .. 49 attend 8 summaries and 9 .. 18 rows of their own
    window."""
    cb = _engine(model, lanes=1)
    try:
        cb.submit(_prompt(40, 3), steps=11).result(timeout=600)
        w = cb.debug_state()["dispatch"]["lane_work"]
    finally:
        cb.shutdown()
    assert w["round"] == {"passes": 4, "rows": 40, "keys": 12 + 24 + 32 + 16,
                          "summary_keys": 8}
    assert w["decode"] == {"passes": 10, "rows": 10,
                           "keys": sum(8 + n for n in range(9, 19)),
                           "summary_keys": 80}


def test_pages_follow_the_rows_at_every_step(model):
    """With one step a dispatch a lane holds exactly the pages of its rows
    and the one the next row lands on: ``ceil((r(p) + 1) / page)``, which
    FALLS by 6 at every boundary (8 pages of rows become 2 of summaries)."""
    cb = _engine(model, lanes=1, decode_block=1)
    seen = []

    def on_token(tok, i):
        lane = cb.debug_state()["lanes"][0]
        if lane["state"] != "idle":          # the last token: released
            seen.append((lane["length"], lane["eva_done"], lane["pages"]))

    try:
        free = cb.pool.free_pages
        cb.submit(_prompt(10, 4), steps=90, on_token=on_token).result(
            timeout=600)
        assert cb.pool.free_pages == free
        assert cb.debug_state()["eva"]["pages_released"] == 3 * 6
    finally:
        cb.shutdown()
    spec = model[0]
    assert len(seen) == 89
    for length, done, pages in seen:
        rows = length - done * (WINDOW - SUMMARIES)
        assert done == max(length - 1, 0) // WINDOW or length % WINDOW == 0
        assert pages == -(-max(rows, 1) // CHUNK) or pages == rows // CHUNK + 1
        if length % WINDOW:
            assert rows == spec.cache_row(length - 1) + 1
    held = [p for _, _, p in seen]
    assert max(held) == WINDOW // CHUNK + 2 * SUMMARIES // CHUNK
    # 31 rows are 8 pages; a row past the boundary 8 summaries and one row
    assert {(31, 0, 8), (33, 1, 3), (63, 1, 10), (65, 2, 5)} <= set(seen)


def test_a_prompt_holds_its_widest_rows_and_no_more(model):
    """All-or-nothing admission secures the most ROWS the prompt will hold
    (its last whole window beside the summaries before it), not its
    positions: 100 positions are 2 x 8 + 32 rows = 12 pages, not 25."""
    cb = _engine(model, lanes=1, n_pages=1 + 12 + 2)
    try:
        prompt = _prompt(100, 5)
        toks = cb.submit(prompt, steps=6).result(timeout=600)
        assert len(toks) == 6 and cb.pool.free_pages == 14
        assert cb.debug_state()["dispatch"]["preemptions"] == 0
    finally:
        cb.shutdown()
    assert cb.max_pages == -(-evabyte_spec(CONFIG).cache_rows_peak(160)
                             // CHUNK) == (4 * 8 + 32) // 4


@pytest.mark.parametrize("use_kernel,junk", [(False, 1e4), (True, np.nan)],
                         ids=["xla-junk", "kernels-nan"])
def test_a_reused_lane_reads_nothing_of_its_predecessor(model, reference,
                                                        use_kernel, junk):
    """Every page a finished request returned is filled with junk (NaN where
    the kernels mask by row; a large number on the gather path, whose masked
    product is 0 x the row): the next request in the lane, and the pages its
    own compactions free and take again, read none of it."""
    cb = _engine(model, use_kernel=use_kernel, lanes=1)
    try:
        cb.submit(_prompt(70, 11), steps=30).result(timeout=600)
        assert cb.pool.free_pages == cb.pool.n_pages - 1
        cb.pool.kv = cb.pool.kv.at[:, 1:].set(junk)
        prompt = _prompt(45, 12)
        toks, lps = cb.submit(prompt, steps=30, logprobs=True).result(
            timeout=600)
    finally:
        cb.shutdown()
    assert np.isfinite(lps).all()
    got = _errors(reference, model, prompt, toks, lps)
    assert got["logprob_err_max"] < 2e-5 and got["argmax_gap"] == 0.0, got


def test_preemption_starts_the_windows_over(model, reference):
    """A preempted request prefills again from position 0: its compacted
    windows are counted from 0 again."""
    cb = _engine(model, lanes=1)
    try:
        prompt = _prompt(50, 13)
        fut = cb.submit(prompt, steps=30, logprobs=True, priority=0)
        while cb.debug_state()["eva"]["compactions"]["round"] < 1:
            pass
        other = cb.submit(_prompt(9, 14), steps=3, priority=5)
        assert len(other.result(timeout=600)) == 3
        toks, lps = fut.result(timeout=600)
        d = cb.debug_state()
    finally:
        cb.shutdown()
    got = _errors(reference, model, prompt, toks, lps)
    assert got["logprob_err_max"] < 2e-5 and got["argmax_gap"] == 0.0, got
    if d["dispatch"]["preemptions"]:
        assert d["eva"]["compactions"]["round"] >= 2


# ---------------------------------------------------------- the refusals ----

@pytest.mark.parametrize("option,value", [
    ("prefix_cache", True), ("kv_offload", True), ("kv_publish", True),
    ("kv_dtype", jnp.float8_e4m3fn),
], ids=lambda v: v if isinstance(v, str) else "")
def test_engine_refuses_by_name_what_does_not_carry_compacted_pages(
        model, option, value):
    with pytest.raises(NotImplementedError, match="EVA windows") as e:
        _engine(model, **{option: value})
    assert option.split("_")[0] in str(e.value)


def test_engine_refuses_a_draft_a_mesh_and_another_page_size(model):
    spec, params = model
    with pytest.raises(NotImplementedError, match="draft_params"):
        _engine(model, draft_params=params)
    with pytest.raises(ValueError, match="eva_chunk"):
        _engine(model, page_size=8)


def test_debug_state_names_the_eva_counters(model):
    cb = _engine(model, lanes=2)
    try:
        gate_seen = []
        fut = cb.submit(_prompt(40, 15), steps=4,
                        on_token=lambda t, i: gate_seen.append(
                            cb.debug_state()["eva"]))
        fut.result(timeout=600)
        eva = cb.debug_state()["eva"]
    finally:
        cb.shutdown()
    assert set(eva) == {"window", "chunk", "compactions", "rows_compacted",
                        "pages_released", "compact_s", "summary_rows_live",
                        "raw_rows_live"}
    assert (eva["window"], eva["chunk"]) == (WINDOW, CHUNK)
    assert eva["compactions"] == {"round": 1, "decode": 0}
    assert eva["rows_compacted"] == WINDOW and eva["compact_s"] > 0
    # while it ran: 8 summaries and the rows of its second window
    live = gate_seen[0]
    assert live["summary_rows_live"] == SUMMARIES
    assert live["raw_rows_live"] == 40 - WINDOW
    assert "compact" in ContinuousBatcher.BREAK_CAUSES
    assert "compact" in ContinuousBatcher.TURN_CAUSES


def test_a_dense_engine_has_no_eva_counters():
    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64, seed=0)
    cb = ContinuousBatcher(params, 2, 1, lanes=1, max_len=32, page_size=4)
    try:
        d = cb.debug_state()
    finally:
        cb.shutdown()
    assert "eva" not in d
    assert set(d["dispatch"]["lane_work"]["decode"]) == {"passes", "rows",
                                                         "keys"}


# ------------------------------ the kernels at the published widths, Mosaic ----

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return shape, lambda *dims: shape(*dims, dtype=jnp.int32)


def test_mosaic_compiles_the_summariser_at_the_published_widths(one_chip):
    """A window of 128 pages of 16 rows of 32 heads x 128, 8 layers of the
    cell's pool; the head loop slices a tile of lanes at a dynamic offset."""
    shape, i32s = _shapes(one_chip)
    text = _summarize_kernel.lower(
        shape(8, 4097, 2, 16, 4096), i32s(128),
        shape(8, 32, 128, dtype=jnp.float32),
        shape(8, 32, 128, dtype=jnp.float32),
        interpret=False).compile().as_text()
    assert "eva_chunk_summary" in text


@pytest.mark.parametrize("rows,name", [(1, "ragged_paged_decode"),
                                       (256, "ragged_paged_attention"),
                                       (512, "ragged_paged_attention")],
                         ids=["decode", "chunk-256", "chunk-512"])
def test_mosaic_compiles_the_kv_kernels_at_the_cells_widths(one_chip, rows,
                                                            name):
    """32 query heads on 32 KV heads of 128 (a group of ONE row a KV head in
    the one-row kernel), 16 lanes, tables of 248 pages."""
    from tpulab.ops.ragged_attention import _ragged_attn
    shape, i32s = _shapes(one_chip)
    lanes, max_pages = 16, 248
    assert max_pages == -(-evabyte_spec(PUBLISHED).cache_rows_peak(32768)
                          // 16)
    text = _ragged_attn.lower(
        shape(lanes, rows, 32, 128), shape(8, 4097, 2, 16, 4096), i32s(1),
        i32s(lanes, max_pages), i32s(lanes), i32s(lanes),
        interpret=False).compile().as_text()
    assert name in text
