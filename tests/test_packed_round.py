"""A mixed round packed by token (PR 29).

``paged_mixed_step`` takes the round as rows = tokens (the prefilling lanes'
chunks one after the other, then a row for each lane's decode token) and runs
everything but the walk over the pages on those rows; the attention is called
once a segment kind, the chunk rows at ``(B, M)`` and the decode rows at ``(B,
1)`` (PR 33).  It is held here to the padded form it replaced,
``paged_ragged_forward(last_only=True)`` on the same segments: the same picks,
last-row logits and pages.  (A model with Mamba layers has no padded form: its
plain form is the decode step, token by token.)  The scheduler half
(``ContinuousBatcher._ragged_round``): lanes that prefill at once share one
token budget, oldest admission first, so the program is keyed by one bucketed
number and a single prompt reaches every bucket.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_evabyte
import test_jamba
import test_keye_sparse
import test_qwen3_next
from helpers_engine import TokenGate, greedy_reference
from helpers_steps import mixed_step
from test_glm_moe import CONFIG, D_FF, VOCAB
from tpulab.engine import paged_steps
from tpulab.engine.kv_pool import LaneStateStore, PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (pack_round, paged_decode_step,
                                       paged_mixed_step,
                                       paged_ragged_forward, round_width)
from tpulab.models.spec import (evabyte_spec, glm4_moe_lite_spec, init_params,
                                jamba_spec, keye_vl2_spec, qwen3_next_spec)
from tpulab.models.transformer import init_transformer_params
from tpulab.ops import ragged_attention

LANES, PAGE, MAX_PAGES = 8, 8, 5
#: lane b owns pages 1 + 5 b .. 5 + 5 b (page 0 is the scratch page)
OWN = 1 + np.arange(LANES * MAX_PAGES, dtype=np.int32).reshape(LANES,
                                                               MAX_PAGES)
i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
#: what a packed step program is bound to beside the model
PACKED = dict(lanes=LANES, max_pages=MAX_PAGES)


def _shared_prefix_tables():
    """Lane 0 reads lane 4's first two pages (a prefix-cache hit) and
    writes its tail to pages of its own."""
    tables = OWN.copy()
    tables[0, :2] = OWN[4, :2]
    return tables


#: segment mixes: ``ctx`` tokens already in each lane's pages (written
#: through the plain form first), ``prefill`` {lane: chunk length} in row
#: order, ``decode`` lanes; a lane in neither is idle
MIXES = {
    "chunk-and-7-decode": dict(
        ctx=[16, 3, 9, 16, 1, 24, 7, 12], prefill={0: 16},
        decode=[1, 2, 3, 4, 5, 6, 7]),
    "two-prompts-share-the-budget": dict(
        ctx=[5, 12, 0, 0, 0, 8, 0, 0], prefill={2: 11, 5: 5},
        decode=[0, 1]),
    "final-1-token-chunk": dict(
        ctx=[9, 4, 0, 16, 0, 0, 0, 0], prefill={3: 1}, decode=[0, 1]),
    "idle-lanes": dict(
        ctx=[0] * 8, prefill={1: 7}, decode=[]),
    "prefix-cache-tail": dict(
        ctx=[0, 0, 0, 0, 20, 0, 6, 0], prefill={0: 5}, decode=[4, 6],
        tables=_shared_prefix_tables(), start={0: 16}),
    # two chunk lanes fill one bucket to its last row, around decode lanes
    # on either side of them
    "two-chunks-fill-the-bucket": dict(
        ctx=[7, 10, 0, 15, 0, 0, 3, 21], prefill={6: 9, 1: 7},
        decode=[0, 3, 7]),
    "no-decode-lane": dict(
        ctx=[0, 0, 11, 0, 0, 17, 0, 0], prefill={5: 6, 2: 3, 7: 2},
        decode=[]),
    # every lane but one idle, and that one mid-prompt across a page edge
    "one-lane-mid-prompt": dict(
        ctx=[0, 0, 0, 0, 0, 0, 0, 13], prefill={7: 9}, decode=[]),
}


@pytest.fixture(scope="module")
def models():
    dense = init_transformer_params(vocab=VOCAB, d_model=64, n_heads=4,
                                    n_layers=2, d_ff=64, n_kv_heads=2,
                                    ffn="swiglu", tie_embeddings=False)
    spec = glm4_moe_lite_spec(CONFIG)
    hybrid = jamba_spec(test_jamba.CONFIG)
    # (params, step arguments, pool arguments, tolerance): the dense golden's
    # fallback, and what tests/test_glm_moe.py and tests/test_jamba.py hold
    # the expert model and the hybrid to
    return {
        "dense": (dense, dict(n_heads=4, n_kv_heads=2, n_layers=2,
                              rope_theta=10000.0),
                  dict(n_heads=2, head_dim=16, n_layers=2), 1e-6),
        "mla-experts": (init_params(spec, VOCAB, D_FF, seed=3, scale=0.1),
                        dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
                             spec=spec),
                        dict(n_heads=0, head_dim=0, n_layers=spec.n_layers,
                             latent_width=spec.latent_width), 2e-5),
        "hybrid": (init_params(hybrid, VOCAB, test_jamba.D_FF, seed=3,
                               scale=0.1),
                   dict(n_heads=hybrid.n_heads, n_layers=hybrid.n_layers,
                        spec=hybrid),
                   dict(n_heads=hybrid.n_kv_heads, head_dim=hybrid.head_dim,
                        n_layers=len(hybrid.attention_layers)), 3e-5),
    }


def _store(kw, pool_kw):
    """A fresh page store; for a model with Mamba layers the pair (page
    store, lane state), the state filled with what another sequence left."""
    kv = PagedKVPool(n_pages=1 + LANES * MAX_PAGES, page_size=PAGE,
                     dtype=jnp.float32, **pool_kw).kv
    spec = kw.get("spec")
    if spec is None or not spec.mamba_layers:
        return kv
    return kv, tuple(jnp.full(a.shape, 3.0, a.dtype) for a in
                     LaneStateStore(spec, LANES, jnp.float32).arrays)


_PROGRAMS = {}


def _jit(fn, model, kw, **static):
    """``fn`` jitted once a (model, attention path): the mixes of a model
    share its compiled shapes (a round is keyed by its bucketed width)
    instead of each compiling programs of its own."""
    key = (fn, model, kw["use_kernel"], tuple(static.items()))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(partial(fn, **static, **kw))
    return _PROGRAMS[key]


def _plain_form(model, params, kw):
    """``plain(kv, tables, seq (LANES, M), q_lens, kv_lens) -> (last logits,
    kv, *moe)``: the padded form, or token by token through the decode
    step where Mamba layers refuse the padded form."""
    spec = kw.get("spec")
    if spec is None or not spec.mamba_layers:
        padded = _jit(paged_ragged_forward, model, kw, last_only=True)
        return lambda kv, tables, seq, q_lens, kv_lens: padded(
            params, kv, tables, i32(seq), i32(q_lens), i32(kv_lens))
    step = _jit(paged_decode_step, model, kw)

    def token_by_token(kv, tables, seq, q_lens, kv_lens):
        last = np.zeros((LANES, VOCAB), np.float32)
        for j in range(int(q_lens.max())):
            active = j < q_lens
            logits, kv = step(params, kv, tables,
                              i32(np.where(active, kv_lens - q_lens + j, 0)),
                              i32(seq[:, j]), jnp.asarray(active))
            last[active] = np.asarray(logits)[active]
        return last, kv
    return token_by_token


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("model,mix", [
    (model, mix) for model in ("dense", "mla-experts", "hybrid")
    for mix in MIXES
    # a lane state is not shared: no prefix-cache hit on a hybrid
    if not (model == "hybrid" and "tables" in MIXES[mix])])
def test_packed_round_is_the_padded_round(models, model, mix, use_kernel):
    params, kw, pool_kw, tol = models[model]
    kw = dict(kw, compute_dtype=jnp.float32, use_kernel=use_kernel)
    case = MIXES[mix]
    rng = np.random.default_rng(5)
    tables = i32(case.get("tables", OWN))
    plain = _plain_form(model, params, kw)
    # the contexts the round finds in the pages
    ctx = np.asarray(case["ctx"], np.int32)
    fill = rng.integers(0, VOCAB, (LANES, max(int(ctx.max()), 1)))
    _logits, kv, *_ = plain(_store(kw, pool_kw), tables, fill, ctx, ctx)
    start = ctx.copy()
    for lane, at in case.get("start", {}).items():
        start[lane] = at            # the shared pages hold its context
    prefill = {lane: rng.integers(0, VOCAB, c)
               for lane, c in case["prefill"].items()}
    decode = {lane: int(rng.integers(VOCAB)) for lane in case["decode"]}
    toks, row_lane, row_off, q_lens = pack_round(LANES, prefill, decode)
    m = round_width(sum(case["prefill"].values()))
    assert len(toks) == m + LANES and (row_lane >= 0).sum() == q_lens.sum()
    kv_lens = np.where(q_lens > 0, start + q_lens, 0)

    picks, _lp, last, kv_packed, *moe = mixed_step(
        _jit(paged_mixed_step, model, kw, **PACKED), params, kv, tables,
        toks, row_lane, row_off, q_lens, kv_lens, spec=kw.get("spec"))

    seq = np.zeros((LANES, m), np.int32)
    for lane, chunk in prefill.items():
        seq[lane, :len(chunk)] = chunk
    for lane, tok in decode.items():
        seq[lane, 0] = tok
    want, kv_plain, *moe_plain = plain(kv, tables, seq, q_lens, kv_lens)
    live = q_lens > 0
    np.testing.assert_allclose(np.asarray(last)[live],
                               np.asarray(want)[live], rtol=tol, atol=tol)
    np.testing.assert_array_equal(
        np.asarray(picks)[live], np.asarray(want).argmax(-1)[live])
    # the same pages written (page 0 is where rows without a token land),
    # and the same lane state where the model keeps one
    for got, ref in zip(jax.tree.leaves(kv_packed), jax.tree.leaves(kv_plain)):
        got, ref = np.asarray(got), np.asarray(ref)
        if got.shape[1] == 1 + LANES * MAX_PAGES:
            got, ref = got[:, 1:], ref[:, 1:]
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
    if moe:      # the expert counters see the rows that hold a token
        np.testing.assert_array_equal(np.asarray(moe[0]),
                                      np.asarray(moe_plain[0]))


@pytest.mark.parametrize("model", ["dense", "mla-experts"])
def test_rows_without_a_token_leave_every_layer_finite(models, model,
                                                       monkeypatch):
    """The kernel leaves the output block of a lane it skips unwritten, and
    on the chip that is whatever the buffer held.  With NaN there, every
    row comes out of every ``_layer_block`` finite (a row without a token
    takes zeros out of the attention) and the round's logits do not move."""
    params, kw, pool_kw, tol = models[model]
    kw = dict(kw, compute_dtype=jnp.float32, use_kernel=True)
    rng = np.random.default_rng(7)
    toks, row_lane, row_off, q_lens = pack_round(
        LANES, {1: rng.integers(0, VOCAB, 7)},          # lane 0 is idle
        {3: int(rng.integers(VOCAB)), 6: int(rng.integers(VOCAB))})
    ctx = np.asarray([0, 5, 0, 9, 0, 0, 2, 0], np.int32)
    plain = _plain_form(model, params, kw)
    _logits, kv, *_ = plain(_store(kw, pool_kw), i32(OWN),
                            rng.integers(0, VOCAB, (LANES, 9)), ctx, ctx)
    args = (params, kv, OWN, toks, row_lane, row_off, q_lens,
            np.where(q_lens > 0, ctx + q_lens, 0))
    want = np.asarray(mixed_step(
        _jit(paged_mixed_step, model, kw, **PACKED), *args,
        spec=kw.get("spec"))[2])

    def unwritten_is_nan(kernel):
        def call(q, kv_pool, layer, tables, q_lens, *rest, **kwargs):
            out = kernel(q, kv_pool, layer, tables, q_lens, *rest, **kwargs)
            return jnp.where((q_lens > 0)[:, None, None, None], out, jnp.nan)
        return call
    for name in ("_ragged_attn", "ragged_latent_attention"):
        monkeypatch.setattr(ragged_attention, name,
                            unwritten_is_nan(getattr(ragged_attention, name)))
    # (the K/V walk's lane loop is a jitted helper, kept a process: here a
    # fresh one, traced over the patched kernel)
    monkeypatch.setattr(paged_steps, "_jitted",
                        paged_steps._jitted.__wrapped__)
    layers, block = [], paged_steps._layer_block

    def spy(*a):
        out = block(*a)
        layers.append(np.asarray(out[0]))
        return out
    monkeypatch.setattr(paged_steps, "_layer_block", spy)
    got = np.asarray(mixed_step(partial(paged_mixed_step, **PACKED, **kw),
                                *args, spec=kw.get("spec"))[2])
    assert len(layers) == kw["n_layers"]
    assert all(np.isfinite(x).all() for x in layers)
    live = q_lens > 0
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


# -- the round takes a carry and returns one ------------------------------------
def _carry_np(carry):
    return [np.asarray(c) for c in carry]


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("model", ["dense", "mla-experts", "hybrid"])
def test_a_round_behind_a_round_takes_its_decode_rows_from_the_carry(
        models, model, use_kernel):
    """Two rounds.  The first, fresh in every lane: lane 0's prompt ENDS in
    it (the host says so with ``rem``), lane 5 is in mid-prompt, lanes 1, 2
    and 3 decode; lane 2's budget ends with this token and lane 3 draws its
    stop token.  Its carry says who goes on: lanes 0 and 1.  The second
    round carries a chunk for lane 5 and a decode row for lanes 0-3 from
    that carry, with a buffer that says nothing of their tokens or lengths:
    it equals the round sent ``fresh`` with the state read back, the rows
    of lanes 2 and 3 are dead (no page and no lane state of theirs moves)
    and its own carry holds them dead."""
    params, kw, pool_kw, tol = models[model]
    kw = dict(kw, compute_dtype=jnp.float32, use_kernel=use_kernel)
    spec = kw.get("spec")
    rng = np.random.default_rng(23)
    tables = i32(OWN)
    plain = _plain_form(model, params, kw)
    ctx = np.asarray([6, 9, 4, 11, 0, 3, 0, 0], np.int32)
    fill = rng.integers(0, VOCAB, (LANES, int(ctx.max())))
    _logits, kv, *_ = plain(_store(kw, pool_kw), tables, fill, ctx, ctx)
    step = _jit(paged_mixed_step, model, kw, **PACKED)

    first = dict(prefill={0: rng.integers(0, VOCAB, 5),
                          5: rng.integers(0, VOCAB, 4)},
                 decode={lane: int(rng.integers(VOCAB)) for lane in (1, 2, 3)})
    toks, row_lane, row_off, q_lens = pack_round(LANES, first["prefill"],
                                                 first["decode"])
    kv_lens = np.where(q_lens > 0, ctx + q_lens, 0)
    rem = np.asarray([7, 4, 1, 9, 0, 0, 0, 0], np.int32)   # lane 5: mid-prompt
    # lane 3's stop token is what it draws here: read it off a dry run
    picks, *_rest = mixed_step(step, params, kv, tables, toks, row_lane,
                               row_off, q_lens, kv_lens, spec=spec, rem=rem)
    stops = np.full((LANES, 2), -1, np.int32)
    stops[3] = [int(picks[3]), -1]
    stops[1] = [(int(picks[1]) + 1) % VOCAB, -1]        # not what lane 1 draws
    picks1, _lp, _last, carry, kv1, *_moe = mixed_step(
        step, params, kv, tables, toks, row_lane, row_off, q_lens, kv_lens,
        spec=spec, rem=rem, stops=stops, with_carry=True)
    np.testing.assert_array_equal(picks1, picks)
    lens, last_toks, live, left = _carry_np(carry)
    np.testing.assert_array_equal(live, [True, True, False, False, False,
                                         False, False, False])
    np.testing.assert_array_equal(lens[:4], (ctx + q_lens)[:4])
    np.testing.assert_array_equal(last_toks[:4], picks[:4])
    np.testing.assert_array_equal(left[:4], rem[:4] - 1)

    # the second round: lane 5's next chunk, a decode row for lanes 0-3
    chunk = {5: rng.integers(0, VOCAB, 3)}
    toks2, row_lane2, row_off2, q2 = pack_round(
        LANES, chunk, {lane: 0 for lane in (0, 1, 2, 3)})
    held = ctx + q_lens                               # what the host knows
    kv2 = np.where(q2 > 0, held + q2, 0)
    kv2[:4] = 0                                       # the carry's to say
    fresh = np.ones((LANES,), bool)
    fresh[:4] = False
    chained = mixed_step(step, params, kv1, tables, toks2, row_lane2,
                         row_off2, q2, kv2, spec=spec, carry=carry,
                         fresh=fresh, stops=stops, with_carry=True)
    # the same round sent fresh: the live lanes alone, with what the carry
    # held read back to the host
    toks3, row_lane3, row_off3, q3 = pack_round(
        LANES, chunk, {lane: int(last_toks[lane]) for lane in (0, 1)})
    kv3 = np.where(q3 > 0, held + q3, 0)
    resent = mixed_step(step, params, kv1, tables, toks3, row_lane3,
                        row_off3, q3, kv3, spec=spec,
                        rem=np.where(np.arange(LANES) < 2, left, 0),
                        stops=stops, with_carry=True)
    ran = q3 > 0
    np.testing.assert_array_equal(chained[0][ran], resent[0][ran])
    np.testing.assert_allclose(np.asarray(chained[2])[ran],
                               np.asarray(resent[2])[ran], rtol=tol, atol=tol)
    for got, want in zip(_carry_np(chained[3]), _carry_np(resent[3])):
        np.testing.assert_array_equal(got[ran], want[ran])
    assert not _carry_np(chained[3])[2][2:].any()       # dead stays dead
    # the pages and lane state: the dead rows wrote nothing of their own
    for got, ref in zip(jax.tree.leaves(chained[4]),
                        jax.tree.leaves(resent[4])):
        got, ref = np.asarray(got), np.asarray(ref)
        if got.shape[1] == 1 + LANES * MAX_PAGES:
            got, ref = got[:, 1:], ref[:, 1:]
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
    if len(chained) > 5:
        np.testing.assert_array_equal(np.asarray(chained[5]),
                                      np.asarray(resent[5]))


def test_a_prompt_that_ends_on_its_window_is_held_for_its_compaction():
    """EVA: a lane whose prompt ends exactly where its window does, and a
    decode row that fills its window, come out of the round not live: the
    block body's hold, so that the compaction comes before the next row."""
    spec = evabyte_spec(test_evabyte.CONFIG)
    window = spec.eva_window
    params = init_params(spec, test_evabyte.VOCAB, test_evabyte.D_FF, seed=3,
                         scale=0.1)
    lanes, page = 3, test_evabyte.CHUNK
    mp = 2 * window // page
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    kv = PagedKVPool(n_pages=1 + lanes * mp, page_size=page,
                     n_layers=spec.n_layers, n_heads=spec.n_kv_heads,
                     head_dim=spec.head_dim, dtype=jnp.float32).kv
    step = jax.jit(partial(paged_mixed_step, lanes=lanes, max_pages=mp,
                           n_heads=spec.n_heads, n_layers=spec.n_layers,
                           compute_dtype=jnp.float32, spec=spec))
    rng = np.random.default_rng(29)
    ctx = np.asarray([window - 6, window - 1, window - 9], np.int32)
    _p, _l, _last, kv, *_ = mixed_step(
        step, params, kv, tables, *pack_round(
            lanes, {b: rng.integers(0, test_evabyte.VOCAB, int(ctx[b]))
                    for b in range(lanes)}, {}), ctx, spec=spec)
    toks, row_lane, row_off, q_lens = pack_round(
        lanes, {0: rng.integers(0, test_evabyte.VOCAB, 6)}, {1: 7, 2: 9})
    out = mixed_step(step, params, kv, tables, toks, row_lane, row_off,
                     q_lens, ctx + q_lens, spec=spec, rem=[5, 5, 5],
                     with_carry=True)
    lens, _toks, live, left = _carry_np(out[3])
    np.testing.assert_array_equal(lens, [window, window, window - 8])
    np.testing.assert_array_equal(live, [False, False, True])
    np.testing.assert_array_equal(left, [4, 4, 4])


# -- the scheduler half ---------------------------------------------------------
def _params():
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=1, d_ff=64)


def _engine(lanes, max_len=512, rope_theta=10000.0, **kw):
    return ContinuousBatcher(_params(), n_heads=2, n_layers=1, lanes=lanes,
                             max_len=max_len, page_size=8,
                             compute_dtype=jnp.float32,
                             rope_theta=rope_theta, **kw)


def _spy_rounds(cb):
    """Record what each mixed round dispatches: its rows, and for every
    lane that had prompt tokens pending its admission number, what was
    pending and what the round took."""
    rounds, mixed = [], cb.programs.mixed

    def spy(params, kv, packed, carry):
        f = paged_steps.unpack_words(cb.programs.fields["round"],
                                     np.asarray(packed))
        toks, row_lane, _row_off = f["rows"]
        q = f["q_lens"]
        m = toks.shape[0] - cb.lanes
        decodes = row_lane[m:] >= 0
        rounds.append(dict(
            rows=int(toks.shape[0]), tokens=int(q.sum()),
            prefill=int((row_lane[:m] >= 0).sum()),
            chunk_lanes=int(((q > 0) & ~decodes).sum()),
            decode_lanes=int(decodes.sum()), width=int(m),
            lanes=[(req.admit_seq, len(req.pending_prompt), int(q[lane]))
                   for lane, req in enumerate(cb._active)
                   if req is not None and req.pf_started]))
        return mixed(params, kv, packed, carry)
    cb.programs.mixed = spy
    return rounds


def test_a_round_shares_one_token_budget_oldest_first():
    cb = _engine(lanes=4, max_len=1024, use_kernel=False)
    rounds = _spy_rounds(cb)
    budget = cb.RAGGED_CHUNK_CAP
    assert budget == cb.debug_state()["dispatch"]["round_budget"] == 512
    rng = np.random.default_rng(3)
    # two more than the lanes; the first two all but fill a round
    lens = [budget + 44, budget - 56, 40, 10, budget + 14, 5]
    try:
        with cb._cv:     # one admission pass sees all six
            futs = [cb.submit(rng.integers(0, 64, n), steps=3)
                    for n in lens]
        outs = [f.result(timeout=120) for f in futs]
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert [len(o) for o in outs] == [3] * len(lens)      # none starved
    shared = 0
    for r in rounds:
        assert 1 <= r["prefill"] <= budget
        assert r["rows"] == round_width(r["prefill"]) + cb.lanes
        left = budget
        for _seq, pending, took in sorted(r["lanes"]):     # oldest first
            assert took == min(pending, left)
            left -= took
        shared += sum(took > 0 for _s, _p, took in r["lanes"]) > 1
    assert shared >= 2            # lanes did prefill in one round
    # every prompt token went through a round once, in as few rounds as
    # the budget allows while prompts were waiting
    assert sum(r["prefill"] for r in rounds) == sum(lens)
    assert len(rounds) >= -(-sum(lens) // budget)
    assert state["mixed_rows"] == sum(r["rows"] for r in rounds)
    assert state["mixed_tokens"] == sum(r["tokens"] for r in rounds)
    assert state["kinds"]["mixed"] == len(rounds)


def test_prefill_chunk_lowers_the_budget_for_all_lanes_together():
    cb = _engine(lanes=3, use_kernel=False, prefill_chunk=16)
    rounds = _spy_rounds(cb)
    rng = np.random.default_rng(4)
    try:
        with cb._cv:
            futs = [cb.submit(rng.integers(0, 64, n), steps=2)
                    for n in (40, 24, 9)]
        for f in futs:
            f.result(timeout=120)
    finally:
        cb.shutdown()
    assert max(r["prefill"] for r in rounds) == 16
    assert max(r["rows"] for r in rounds) == 16 + 3
    assert sum(r["prefill"] for r in rounds) == 40 + 24 + 9


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
def test_seeded_mixed_workload_streams_equal_the_plain_forwards(use_kernel):
    """Greedy streams of staggered prompts, short and long, in rounds they
    share against a reference that shares no code with the engine: the
    plain forward over each whole sequence, a token at a time
    (``helpers_engine.greedy_reference``)."""
    rng = np.random.default_rng(11)
    lens = [70, 5, 33, 130, 17, 9, 64, 1]
    prompts = [rng.integers(0, 64, n) for n in lens]

    def run(**kw):
        cb = _engine(lanes=3, max_len=256, **kw)
        try:
            futs = []
            for i, p in enumerate(prompts):
                futs.append(cb.submit(p, steps=6 + i % 4))
                if i % 3 == 2:      # arrivals in bursts of three
                    futs[-1].result(timeout=120)
            return [list(f.result(timeout=120)) for f in futs], cb
        finally:
            cb.shutdown()
    want = [greedy_reference(_params(), p, 6 + i % 4, 2, 1,
                             rope_theta=10000.0)[0]
            for i, p in enumerate(prompts)]
    # a budget of 32 makes the long prompts share rounds with the short
    got, cb = run(use_kernel=use_kernel, prefill_chunk=32)
    assert got == want
    assert cb.dispatch_kinds["mixed"] >= -(-sum(lens) // 32)
    assert 0 < cb.mixed_tokens <= cb.mixed_rows
    # the rounds ran as members of the chain (every family, greedy and
    # sampled, against the plan that fetches every dispatch:
    # test_chained_rounds_give_the_streams_of_the_plan_that_fetches_every_dispatch)
    assert cb.ahead_rounds > 0 and cb.mixed_decode_rows > 0


def _dense_family():
    return None, init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                         n_layers=1, d_ff=64), 64


def _spec_family(make_spec, module, d_ff, scale=0.1):
    def build():
        spec = make_spec(module.CONFIG)
        return (spec, init_params(spec, module.VOCAB, d_ff, seed=3,
                                  scale=scale), module.VOCAB)
    return build


#: one tiny model of every family the engine serves: (spec, params, vocab)
FAMILIES = {
    "dense": _dense_family,
    "latent-experts": lambda: (
        glm4_moe_lite_spec(CONFIG),
        init_params(glm4_moe_lite_spec(CONFIG), VOCAB, D_FF, seed=3,
                    scale=0.1), VOCAB),
    "mamba": _spec_family(jamba_spec, test_jamba, test_jamba.D_FF),
    "gated-deltanet": _spec_family(qwen3_next_spec, test_qwen3_next, 0),
    "sparse": _spec_family(keye_vl2_spec, test_keye_sparse, 0, scale=0.3),
    "eva": _spec_family(evabyte_spec, test_evabyte, test_evabyte.D_FF),
}


def _family_engine(family, spec, params, **kw):
    heads = (2, 1) if spec is None else (spec.n_heads, spec.n_layers)
    geometry = (dict(max_len=160, page_size=test_evabyte.CHUNK,
                     prefill_chunk=12) if family == "eva"
                else dict(max_len=96, page_size=8, prefill_chunk=8))
    return ContinuousBatcher(params, *heads, spec=spec, lanes=3,
                             compute_dtype=jnp.float32,
                             use_kernel=False, **dict(geometry, **kw))


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_chained_rounds_give_the_streams_of_the_plan_that_fetches_every_dispatch(
        family, sampled, monkeypatch):
    """A seeded mixed workload (prompts of one to several rounds arriving
    beside lanes that decode) on a tiny model of every family: with the
    round a member of the chain (rounds enqueued behind un-fetched blocks
    and rounds, their decode rows from the device carry) every stream is,
    token for token, that of the engine whose every dispatch is fetched
    before the next is planned."""
    from tpulab.engine.paged import SamplingParams
    spec, params, vocab = FAMILIES[family]()
    rng = np.random.default_rng(37)
    lens = [9, 30, 5, 21, 14, 3, 26, 11]
    if family == "eva":
        lens = [9, 70, 5, 33, 14, 3, 40, 11]     # past a window, and two
    prompts = [rng.integers(0, vocab, n) for n in lens]

    def run(chain):
        if not chain:
            monkeypatch.setattr(ContinuousBatcher, "_chain_block",
                                lambda self, stash, jnp, ahead: (None, "k1"))
        cb = _family_engine(family, spec, params)
        try:
            futs = []
            for i, p in enumerate(prompts):
                sub = dict(sampling=SamplingParams(
                    temperature=0.9, seed=100 + i, device=True)) \
                    if sampled else {}
                futs.append(cb.submit(p, steps=9 + 5 * (i % 3), **sub))
                if i % 3 == 2:      # arrivals in bursts of three
                    futs[-2].result(timeout=300)
            return ([list(f.result(timeout=300)) for f in futs],
                    cb.debug_state()["dispatch"])
        finally:
            cb.shutdown()
            monkeypatch.undo()
    got, state = run(True)
    want, plain = run(False)
    assert got == want
    assert [len(t) for t in got] == [9 + 5 * (i % 3)
                                     for i in range(len(lens))]
    assert plain["ahead_rounds"] == plain["ahead_blocks"] == 0
    assert state["ahead_rounds"] >= 3 and state["rounds_after_round"] >= 1
    assert state["mixed_decode_rows"] > 0
    assert state["chain"]["breaks"]["joiner"] == 0
    if family == "eva":
        assert state["chain"]["breaks"]["compact"] >= 2


def test_mixed_attn_rows_counts_m_a_chunk_lane_and_one_a_decode_lane():
    """``debug_state()["dispatch"]["mixed_attn_rows"]`` is what the rounds'
    attention calls computed a layer: the round's width for each lane that
    held a chunk, one row for each decoding lane, nothing for an idle lane
    (a single call computed ``lanes x M``)."""
    cb = _engine(lanes=4, max_len=256, use_kernel=False,
                 prefill_chunk=32)
    rounds = _spy_rounds(cb)
    rng = np.random.default_rng(13)
    streaming = TokenGate(3)
    try:
        # two lanes decode for long; prompts of one to three chunks arrive
        # beside them, three at a time on the two lanes left
        futs = [cb.submit(rng.integers(0, 64, 40), steps=90,
                          on_token=streaming),
                cb.submit(rng.integers(0, 64, 5), steps=90)]
        assert streaming.wait(60)
        for burst in ((70, 33, 9), (64, 17, 1)):
            with cb._cv:     # one admission pass sees the burst
                futs += [cb.submit(rng.integers(0, 64, n), steps=3)
                         for n in burst]
            streaming.release()
            futs[-1].result(timeout=120)
        for f in futs:
            f.result(timeout=120)
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert any(r["decode_lanes"] for r in rounds)
    assert any(r["chunk_lanes"] > 1 for r in rounds)
    assert any(r["chunk_lanes"] + r["decode_lanes"] < cb.lanes
               for r in rounds)
    assert state["mixed_attn_rows"] == sum(
        r["width"] * r["chunk_lanes"] + r["decode_lanes"] for r in rounds)
    assert (state["mixed_tokens"] <= state["mixed_attn_rows"]
            < sum(r["width"] * cb.lanes for r in rounds))
    # ... and the chunk lanes themselves, a call of the K/V walk each (PR
    # 59): more than one a round here, never more than the lanes
    assert state["round_chunk_lanes"] == sum(r["chunk_lanes"] for r in rounds)
    assert len(rounds) == state["kinds"]["mixed"] < state["round_chunk_lanes"]


def test_every_mixed_program_is_reached_by_a_single_prompt():
    """The harness's warm-up (``perf/models/lm.py``) reads the engine's
    ``RAGGED_CHUNK_CAP`` and sends single prompts of ``cap + b`` tokens, b
    every power of two under it, and one of ``cap``: after it the mixed
    program's cache holds one entry a power of two from 2 up to the budget
    (a one-token tail pads to two rows: nine at 512, as there were at
    256), and a burst of concurrent prompts adds none."""
    # a rope_theta of its own: the jitted program is shared by engines of
    # one geometry, and this test counts its cache
    cb = _engine(lanes=4, max_len=1024, rope_theta=29.0,
                 use_kernel=False)
    cap = cb.RAGGED_CHUNK_CAP
    tails = [1 << i for i in range(cap.bit_length())]
    assert tails[-1] == cap == 512
    rng = np.random.default_rng(2)
    try:
        for b in tails:
            n = cap + b if b < cap else cap
            cb.submit(rng.integers(0, 64, n), steps=2).result(timeout=120)
        assert cb.programs.mixed._cache_size() == len(tails) - 1 == 9
        futs = [cb.submit(rng.integers(0, 64, n), steps=12)
                for n in (64, 64, 64, 64, 64, 64, 64, 64, 300, 7, 250, 129,
                          700, 1)]
        for f in futs:
            f.result(timeout=120)
        # with or without a predecessor: the burst's rounds went behind
        # un-fetched blocks and rounds, on the same nine programs
        assert cb.programs.mixed._cache_size() == 9
        assert cb.ahead_rounds > 0 and cb.mixed_decode_rows > 0
    finally:
        cb.shutdown()


# -- the round's budget: derived from the shapes, counted where it engages ------
@pytest.mark.parametrize("kw", [
    dict(use_kernel=False), dict(use_kernel=True),
    dict(use_kernel=False, prefill_chunk=64)],
    ids=["ceiling-gather", "ceiling-kernel", "prefill_chunk-64"])
def test_a_long_prompt_takes_whole_budgets_then_its_tail(kw):
    """A prompt of ``2 x budget + 40`` is three rounds of ``budget, budget,
    40`` tokens, and its greedy stream is the plain forward's over the
    whole sequence (``helpers_engine.greedy_reference``: no code of the
    engine's)."""
    rng = np.random.default_rng(17)
    cb = _engine(lanes=2, max_len=1200, **kw)
    rounds = _spy_rounds(cb)
    try:
        prompt = rng.integers(0, 64, 2 * cb._round_budget + 40)
        got = list(cb.submit(prompt, steps=5).result(timeout=300))
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    budget = state["round_budget"]
    assert budget == kw.get("prefill_chunk", 512)
    assert [r["prefill"] for r in rounds] == [budget, budget, 40]
    assert [r["width"] for r in rounds] == [budget, budget, 64]
    assert (state["budget_rounds"], state["mixed_prompt_tokens"]) == (
        2, len(prompt))
    assert got == greedy_reference(_params(), prompt, 5, 2, 1,
                                   rope_theta=10000.0)[0]


def _geometry_engine(**kw):
    """Two heads of 64 on pages of 8 rows: a page row of 128 lanes, the
    smallest geometry the rows kernel's rule admits."""
    params = init_transformer_params(vocab=64, d_model=128, n_heads=2,
                                     n_layers=1, d_ff=64)
    return ContinuousBatcher(params, n_heads=2, n_layers=1, lanes=2,
                             max_len=1024, page_size=8,
                             compute_dtype=jnp.float32, use_kernel=True, **kw)


@pytest.mark.parametrize("admits,kw,budget", [
    (512, {}, 512), (256, {}, 256), (64, {}, 64),
    (256, dict(prefill_chunk=96), 96), (64, dict(prefill_chunk=16), 16)],
    ids=["admits-512", "refuses-512", "refuses-128", "chunk-under-the-cap",
         "chunk-fits-where-the-cap-would-not"])
def test_the_budget_is_the_widest_round_the_geometry_rule_admits(
        monkeypatch, admits, kw, budget):
    """The engine asks its kernels' rule at construction, the ceiling first
    and then each power of two under it, and says in ``debug_state()`` what
    it got and what refused the next wider round.  ``prefill_chunk`` lowers
    the budget and is what the rule is asked about: a chunk of 16 pads to a
    round of 16 rows whatever the cap."""
    def plan(rows):
        return ragged_attention._plan(rows, 2, 2, 64, 8, 128, jnp.float32,
                                      jnp.float32)[2]
    monkeypatch.setattr(ragged_attention, "_VMEM_REQUEST_MAX", plan(admits))
    cb = _geometry_engine(**kw)
    try:
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    chunk = kw.get("prefill_chunk")
    cap = 512 if chunk and round_width(chunk) <= admits else admits
    assert cb.RAGGED_CHUNK_CAP == cap
    assert ContinuousBatcher.RAGGED_CHUNK_CAP == 512      # the ceiling
    assert state["round_budget"] == cb._round_budget == budget
    assert state["use_kernel"]
    if cap == 512:
        assert state["round_budget_why"] is None
    else:
        assert f"q_len={2 * cap}" in state["round_budget_why"]
        assert "VMEM" in state["round_budget_why"]


@pytest.mark.parametrize("max_len,budget", [
    (1024, 512), (1023, 256), (640, 256), (256, 128), (24, 8)])
def test_twice_the_budget_fits_max_len(max_len, budget):
    """Each width's program is reached by one prompt that spends the
    budget and leaves a tail of that width (``perf/models/lm.py`` warms
    them so): an engine whose ``max_len`` does not hold twice the ceiling
    takes the widest power of two it does hold twice, and says so."""
    cb = _engine(lanes=2, max_len=max_len, use_kernel=False)
    try:
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert state["round_budget"] == cb.RAGGED_CHUNK_CAP == budget
    assert (state["round_budget_why"] is None) == (budget == 512)
    if budget < 512:
        assert f"max_len {max_len}" in state["round_budget_why"]


def test_a_geometry_that_admits_no_round_is_still_refused_by_name(
        monkeypatch):
    """Where the rule refuses every width (here: the one-row kernel's own
    VMEM) nothing is narrowed: a compiled engine is refused with the
    rule's words, as it was; the interpreter, which the rule does not
    bind, runs the ceiling."""
    from tpulab.tpu import platform
    monkeypatch.setattr(ragged_attention, "_VMEM_REQUEST_MAX", 1 << 10)
    cb = _geometry_engine()
    try:
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert (state["round_budget"], state["round_budget_why"]) == (512, None)
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    with pytest.raises(ValueError, match="use_kernel=True: kernel VMEM .* "
                                         "for one row a lane"):
        _geometry_engine()


@pytest.mark.parametrize("chunk", [None, 32], ids=["ceiling", "chunk-32"])
def test_budget_counters_count_what_the_spy_sees(chunk):
    """``round_budget`` is the budget the planner reads, and
    ``mixed_prompt_tokens`` / ``budget_rounds`` the prompt tokens the rounds
    carried (``mixed_tokens`` less the decode rows) and the rounds that
    spent the whole budget."""
    cb = _engine(lanes=3, max_len=1400, use_kernel=False,
                 prefill_chunk=chunk)
    rounds = _spy_rounds(cb)
    budget = cb._round_budget
    rng = np.random.default_rng(19)
    streaming = TokenGate(3)
    try:
        # one lane decodes while prompts of under one to over two budgets
        # arrive beside it, two at a time
        futs = [cb.submit(rng.integers(0, 64, 9), steps=60,
                          on_token=streaming)]
        assert streaming.wait(60)
        for burst in ((2 * budget + 7, budget // 2), (budget, 3)):
            with cb._cv:
                futs += [cb.submit(rng.integers(0, 64, n), steps=3)
                         for n in burst]
            streaming.release()
            futs[-1].result(timeout=300)
        for f in futs:
            f.result(timeout=300)
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert state["round_budget"] == budget == (chunk or 512)
    assert state["mixed_prompt_tokens"] == sum(r["prefill"] for r in rounds)
    assert state["mixed_prompt_tokens"] == state["mixed_tokens"] - sum(
        r["decode_lanes"] for r in rounds)
    spent = sum(r["prefill"] == budget for r in rounds)
    assert state["budget_rounds"] == spent >= 3
    assert any(r["decode_lanes"] for r in rounds)
