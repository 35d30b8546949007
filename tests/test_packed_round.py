"""A mixed round packed by token (PR 29).

``paged_mixed_step`` takes the round as rows = tokens (the prefilling lanes'
chunks one after the other, then a row for each lane's decode token) and runs
everything but the walk over the pages on those rows.  It is held here to the
padded form it replaced, ``paged_ragged_forward(last_only=True)`` on the same
segments: the same picks, last-row logits and pages.  The scheduler half
(``ContinuousBatcher._ragged_round``): lanes that prefill at once share one
token budget, oldest admission first, so the program is keyed by one bucketed
number and a single prompt reaches every bucket.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_glm_moe import CONFIG, D_FF, VOCAB
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (pack_round, paged_mixed_step,
                                       paged_ragged_forward, round_width)
from tpulab.models.spec import glm4_moe_lite_spec, init_params
from tpulab.models.transformer import init_transformer_params

LANES, PAGE, MAX_PAGES = 8, 8, 5
#: lane b owns pages 1 + 5 b .. 5 + 5 b (page 0 is the scratch page)
OWN = 1 + np.arange(LANES * MAX_PAGES, dtype=np.int32).reshape(LANES,
                                                               MAX_PAGES)


def _shared_prefix_tables():
    """Lane 0 reads lane 4's first two pages (a prefix-cache hit) and
    writes its tail to pages of its own."""
    tables = OWN.copy()
    tables[0, :2] = OWN[4, :2]
    return tables


#: segment mixes: ``ctx`` tokens already in each lane's pages (written
#: through the padded form first), ``prefill`` {lane: chunk length} in row
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
}


@pytest.fixture(scope="module")
def models():
    dense = init_transformer_params(vocab=VOCAB, d_model=64, n_heads=4,
                                    n_layers=2, d_ff=64, n_kv_heads=2,
                                    ffn="swiglu", tie_embeddings=False)
    spec = glm4_moe_lite_spec(CONFIG)
    # (params, step arguments, pool arguments, tolerance): the dense golden's
    # fallback, and what tests/test_glm_moe.py holds the expert model to
    return {
        "dense": (dense, dict(n_heads=4, n_kv_heads=2, n_layers=2,
                              rope_theta=10000.0),
                  dict(n_heads=2, head_dim=16), 1e-6),
        "mla-experts": (init_params(spec, VOCAB, D_FF, seed=3, scale=0.1),
                        dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
                             spec=spec),
                        dict(n_heads=0, head_dim=0,
                             latent_width=spec.latent_width), 2e-5),
    }


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("model", ["dense", "mla-experts"])
def test_packed_round_is_the_padded_round(models, model, use_kernel, mix):
    params, kw, pool_kw, tol = models[model]
    kw = dict(kw, compute_dtype=jnp.float32, use_kernel=use_kernel)
    case = MIXES[mix]
    rng = np.random.default_rng(5)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    tables = i32(case.get("tables", OWN))
    kv = PagedKVPool(n_pages=1 + LANES * MAX_PAGES, page_size=PAGE,
                     n_layers=kw["n_layers"], dtype=jnp.float32,
                     **pool_kw).kv
    padded = jax.jit(partial(paged_ragged_forward, last_only=True, **kw))
    # the contexts the round finds in the pages
    ctx = np.asarray(case["ctx"], np.int32)
    fill = rng.integers(0, VOCAB, (LANES, max(int(ctx.max()), 1)))
    _logits, kv, *_ = padded(params, kv, tables, i32(fill), i32(ctx),
                             i32(ctx))
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
    temps = jnp.zeros((LANES,), jnp.float32)
    seeds = jnp.zeros((LANES, 2), jnp.uint32)

    picks, _lp, last, kv_packed, *moe = jax.jit(
        partial(paged_mixed_step, **kw))(
            params, kv, tables, i32(toks), i32(row_lane), i32(row_off),
            i32(q_lens), i32(kv_lens), temps, seeds)

    seq = np.zeros((LANES, m), np.int32)
    for lane, chunk in prefill.items():
        seq[lane, :len(chunk)] = chunk
    for lane, tok in decode.items():
        seq[lane, 0] = tok
    want, kv_padded, *moe_padded = padded(params, kv, tables, i32(seq),
                                          i32(q_lens), i32(kv_lens))
    live = q_lens > 0
    np.testing.assert_allclose(np.asarray(last)[live],
                               np.asarray(want)[live], rtol=tol, atol=tol)
    np.testing.assert_array_equal(
        np.asarray(picks)[live], np.asarray(want).argmax(-1)[live])
    # the same pages written (page 0 is where rows without a token land)
    np.testing.assert_allclose(np.asarray(kv_packed)[:, 1:],
                               np.asarray(kv_padded)[:, 1:], rtol=tol,
                               atol=tol)
    if moe:      # the expert counters see the rows that hold a token
        np.testing.assert_array_equal(np.asarray(moe[0]),
                                      np.asarray(moe_padded[0]))


# -- the scheduler half ---------------------------------------------------------
def _engine(lanes, max_len=512, rope_theta=10000.0, **kw):
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    return ContinuousBatcher(params, n_heads=2, n_layers=1, lanes=lanes,
                             max_len=max_len, page_size=8,
                             compute_dtype=jnp.float32,
                             rope_theta=rope_theta, **kw)


def _spy_rounds(cb):
    """Record what each mixed round dispatches: its rows, and for every
    lane that had prompt tokens pending its admission number, what was
    pending and what the round took."""
    rounds, mixed = [], cb._mixed

    def spy(params, kv, tables, toks, row_lane, row_off, q_lens, kv_lens,
            *rest):
        q = np.asarray(q_lens)
        rounds.append(dict(
            rows=int(toks.shape[0]), tokens=int(q.sum()),
            prefill=int((np.asarray(row_lane)[:toks.shape[0] - cb.lanes]
                         >= 0).sum()),
            lanes=[(req.admit_seq, len(req.pending_prompt), int(q[lane]))
                   for lane, req in enumerate(cb._active)
                   if req is not None and req.pf_started]))
        return mixed(params, kv, tables, toks, row_lane, row_off, q_lens,
                     kv_lens, *rest)
    cb._mixed = spy
    return rounds


def test_a_round_shares_one_token_budget_oldest_first():
    cb = _engine(lanes=4, ragged=True, use_kernel=False)
    rounds = _spy_rounds(cb)
    budget = cb.RAGGED_CHUNK_CAP
    rng = np.random.default_rng(3)
    lens = [300, 200, 40, 10, 270, 5]       # two more than the lanes
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
    cb = _engine(lanes=3, ragged=True, use_kernel=False, prefill_chunk=16)
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
def test_seeded_mixed_workload_streams_equal_the_split_plans(use_kernel):
    """Greedy streams of staggered prompts, short and long, under the
    ragged plan against the legacy split plan, which this change does not
    touch: the parent's tokens."""
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
    want, _ = run(ragged=False, use_kernel=False)
    # a budget of 32 makes the long prompts share rounds with the short
    got, cb = run(ragged=True, use_kernel=use_kernel, prefill_chunk=32)
    assert got == want
    assert cb.dispatch_kinds["mixed"] >= -(-sum(lens) // 32)
    assert 0 < cb.mixed_tokens <= cb.mixed_rows


def test_nine_mixed_programs_all_reached_by_single_prompts():
    """The harness's warm-up (``perf/models/lm.py``) sends single prompts
    of ``256 + b`` tokens, b a power of two up to 128, and one of 256:
    after it the mixed program's cache holds nine entries, and a burst of
    concurrent prompts adds none."""
    # a rope_theta of its own: the jitted program is shared by engines of
    # one geometry, and this test counts its cache
    cb = _engine(lanes=4, max_len=512, rope_theta=29.0, ragged=True,
                 use_kernel=False)
    cap = cb.RAGGED_CHUNK_CAP
    rng = np.random.default_rng(2)
    try:
        for b in [1, 2, 4, 8, 16, 32, 64, 128, 256]:
            n = cap + b if b < cap else cap
            cb.submit(rng.integers(0, 64, n), steps=2).result(timeout=120)
        assert cb._mixed._cache_size() == 9
        futs = [cb.submit(rng.integers(0, 64, n), steps=12)
                for n in (64, 64, 64, 64, 64, 64, 64, 64, 300, 7, 250, 129)]
        for f in futs:
            f.result(timeout=120)
        assert cb._mixed._cache_size() == 9
    finally:
        cb.shutdown()
