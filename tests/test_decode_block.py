"""Multi-step fused decode (K-token device blocks) tests.

Parity discipline: K=1 and K>1 must be TOKEN-IDENTICAL — the block is a
dispatch-shape change, never a sampling-semantics change.  Greedy argmax
and the (seed, position)-folded device-sampling stream both depend only
on per-lane state the scan carries exactly, so equality is exact, not
approximate.  The host-sync guard pins the whole point of the feature:
one blocking fetch per K tokens, not per token.
"""

import math
import time as _time

import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine.paged import (ContinuousBatcher, SamplingParams,
                                 _PagedRequest)
from tpulab.models.transformer import init_transformer_params, make_generate_fn


@pytest.fixture(scope="module")
def lm():
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)


def _batcher(lm, k, **kw):
    kw.setdefault("lanes", 2)
    kw.setdefault("max_len", 64)
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, page_size=8,
                             compute_dtype=jnp.float32, decode_block=k,
                             **kw)


def test_block_greedy_parity_with_page_crossings(lm):
    """K=8 greedy == K=1 greedy == dense, including decode runs that
    cross page boundaries INSIDE a block (page_size 8, prompts that put
    the write position mid-page at block start)."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    outs = {}
    for k in (1, 8):
        cb = _batcher(lm, k)
        try:
            rng = np.random.default_rng(5)
            cases = [(rng.integers(0, 64, (n,), np.int32), s)
                     for n, s in ((5, 20), (8, 17), (13, 30), (1, 9))]
            outs[k] = [list(cb.submit(p, s).result(timeout=120))
                       for p, s in cases]
            if k == 1:
                for (p, s), got in zip(cases, outs[k]):
                    np.testing.assert_array_equal(
                        np.asarray(got), np.asarray(dense(p[None, :], s)[0]))
        finally:
            cb.shutdown()
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    assert outs[8] == outs[1]


def test_block_device_sampled_parity(lm):
    """Seeded device-sampled streams are identical at K=1 and K=8: the
    sampling key folds (seed, position) only, and the scan advances
    positions exactly as single ticks do."""
    p = np.random.default_rng(6).integers(0, 64, (5,), np.int32)
    outs = {}
    for k in (1, 8):
        cb = _batcher(lm, k)
        try:
            outs[k] = list(cb.submit(
                p, 20, sampling=SamplingParams(temperature=0.9, seed=1234,
                                               device=True)
            ).result(timeout=120))
        finally:
            cb.shutdown()
    assert outs[8] == outs[1] and len(outs[8]) == 20


def test_block_eos_mid_block(lm):
    """A stop token hit mid-block ends the lane ON DEVICE: the stop token
    is the final emitted token (host contract), later scan steps emit
    nothing, and the lane's pages all come home."""
    p = np.random.default_rng(8).integers(0, 64, (5,), np.int32)
    cb1 = _batcher(lm, 1)
    try:
        ref = list(cb1.submit(p, 16).result(timeout=120))
    finally:
        cb1.shutdown()
    stop = ref[5]          # greedy run's 6th token -> stops mid first block
    want = ref[:ref.index(stop) + 1]
    cb = _batcher(lm, 8)
    try:
        got = list(cb.submit(p, 16, stop_tokens=[stop]).result(timeout=120))
        assert got == want
        # stop at the PREFILL-emitted first token still terminates
        got1 = list(cb.submit(p, 16,
                              stop_tokens=[ref[0]]).result(timeout=120))
        assert got1 == ref[:1]
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_block_steps_limit_mid_block(lm):
    """steps smaller than (and not divisible by) K: the device-side
    steps-remaining mask stops the lane exactly at the budget."""
    p = np.random.default_rng(9).integers(0, 64, (4,), np.int32)
    cb1 = _batcher(lm, 1)
    try:
        refs = {s: list(cb1.submit(p, s).result(timeout=120))
                for s in (2, 5, 9)}
    finally:
        cb1.shutdown()
    cb = _batcher(lm, 8)
    try:
        for s, want in refs.items():
            got = list(cb.submit(p, s).result(timeout=120))
            assert got == want and len(got) == s
    finally:
        cb.shutdown()


def test_block_logprobs_parity(lm):
    """logprobs=True through the block path: same tokens, same on-device
    log-softmax stream as K=1 (allclose: the scan may fuse differently)."""
    p = np.random.default_rng(12).integers(0, 64, (6,), np.int32)
    outs = {}
    for k in (1, 8):
        cb = _batcher(lm, k)
        try:
            outs[k] = cb.submit(p, 12, logprobs=True).result(timeout=120)
        finally:
            cb.shutdown()
    assert list(outs[8][0]) == list(outs[1][0])
    np.testing.assert_allclose(outs[8][1], outs[1][1], rtol=1e-5,
                               atol=1e-6)


def test_block_prefix_cache_shared_pages_stay_clean(lm):
    """Prefix-cache-hit lanes under K=8: block appends only ever write the
    lane's private tail — repeated and branched prompts keep producing
    the exact uncached sequences even AFTER earlier hits decoded full
    blocks (a clobbered shared page would corrupt the later hits)."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = _batcher(lm, 8, lanes=1, prefix_cache=True)
    try:
        rng = np.random.default_rng(3)
        base = rng.integers(0, 64, (20,), np.int32)     # 2 full pages + 4
        got1 = list(cb.submit(base, 16).result(timeout=120))
        hits0 = cb.prefix_cache.hits
        got2 = list(cb.submit(base, 16).result(timeout=120))
        assert cb.prefix_cache.hits - hits0 == 2        # both pages shared
        branch = np.concatenate([base[:16],
                                 rng.integers(0, 64, (7,), np.int32)])
        got3 = list(cb.submit(branch, 16).result(timeout=120))
        got4 = list(cb.submit(base, 16).result(timeout=120))  # hit again
        for p, got in ((base, got1), (base, got2), (branch, got3),
                       (base, got4)):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(dense(p[None, :], 16)[0]))
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_block_host_sampling_drops_to_single_step(lm):
    """A host-sampled (top_k) lane in the batch forces K=1 for the whole
    dispatch: its seeded stream must equal the decode_block=1 reference
    even while a greedy lane shares the batch."""
    ph = np.random.default_rng(2).integers(0, 64, (4,), np.int32)
    pg = np.random.default_rng(1).integers(0, 64, (4,), np.int32)
    cb1 = _batcher(lm, 1, lanes=1)
    try:
        want = list(cb1.submit(
            ph, 10, sampling=SamplingParams(temperature=0.8, top_k=8,
                                            seed=55)).result(timeout=120))
    finally:
        cb1.shutdown()
    cb = _batcher(lm, 8, lanes=2)
    try:
        fh = cb.submit(ph, 10, sampling=SamplingParams(
            temperature=0.8, top_k=8, seed=55))
        fg = cb.submit(pg, 10)
        assert list(fh.result(timeout=120)) == want
        assert len(fg.result(timeout=120)) == 10
    finally:
        cb.shutdown()


def test_block_streaming_callbacks_in_order(lm):
    """Per-token on_token callbacks survive block unpacking: every token,
    in order, with its index — and the final future matches the stream."""
    cb = _batcher(lm, 8, lanes=1)
    try:
        streamed = []
        p = np.random.default_rng(4).integers(0, 64, (4,), np.int32)
        fut = cb.submit(p, 13,
                        on_token=lambda tok, i: streamed.append((i, tok)))
        final = fut.result(timeout=120)
        assert [i for i, _t in streamed] == list(range(13))  # in order
        assert [t for _i, t in streamed] == list(final)
    finally:
        cb.shutdown()


def test_host_sync_budget_per_request(lm):
    """Regression guard against reintroducing per-token host syncs: a
    greedy request's blocking decode fetches stay <= ceil(steps/K), plus
    one prefill pass (counted separately)."""
    cb = _batcher(lm, 8, lanes=1)
    try:
        p = np.random.default_rng(7).integers(0, 64, (5,), np.int32)
        cb.submit(p, 17).result(timeout=120)   # warm compiles
        s0, d0 = cb.decode_host_syncs, cb.decode_dispatches
        pf0, tg0 = cb.prefill_dispatches, cb.tokens_generated
        out = cb.submit(p, 17).result(timeout=120)
        assert len(out) == 17
        syncs = cb.decode_host_syncs - s0
        budget = math.ceil(17 / cb.decode_block)
        assert syncs <= budget, (syncs, budget)
        assert cb.decode_dispatches - d0 <= budget
        assert cb.prefill_dispatches - pf0 == 1
        # and the telemetry ratio reflects the amortization
        toks = cb.tokens_generated - tg0
        assert toks == 17 and syncs / toks < 0.2
    finally:
        cb.shutdown()


def test_pick_block_k_policy(lm):
    """Adaptive K: host sampling -> 1; tight deadline -> <=2; streaming
    consumer without queue pressure -> <=2; batch consumers -> full
    ceiling; never longer than the remaining step budget needs."""
    cb = _batcher(lm, 16, lanes=1)
    try:
        def req(**kw):
            r = _PagedRequest(np.ones(4, np.int32), kw.pop("steps", 40),
                              **kw)
            r.tokens_out = [1]
            return r

        assert cb._pick_block_k([(0, req())]) == 16
        host = req(sampling=SamplingParams(temperature=0.8, top_k=4,
                                           seed=1))
        assert cb._pick_block_k([(0, req()), (1, host)]) == 1
        tight = req()
        tight.deadline = _time.monotonic() + 0.001
        assert cb._pick_block_k([(0, tight)]) <= 2
        loose = req()
        loose.deadline = _time.monotonic() + 300.0
        assert cb._pick_block_k([(0, loose)]) == 16
        stream = req(on_token=lambda t, i: None)
        assert cb._pick_block_k([(0, stream)]) <= 2
        # steps-remaining clamp: 3 tokens left never dispatches K=16
        short = req(steps=4)            # 1 emitted, 3 remaining
        assert cb._pick_block_k([(0, short)]) == 4
    finally:
        cb.shutdown()


def test_block_under_page_pressure_shrinks_not_starves(lm):
    """A pool too tight for full K-blocks still completes every request
    (the reserve shrinks the block / skips starved lanes instead of
    wedging), and all pages come home."""
    cb = _batcher(lm, 8, lanes=2, max_len=32, n_pages=7)  # 6 usable pages
    try:
        rng = np.random.default_rng(11)
        futs = [cb.submit(rng.integers(0, 64, (6,), np.int32), 16)
                for _ in range(4)]
        for f in futs:
            assert len(f.result(timeout=120)) == 16
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_block_deadline_expiry_within_one_block(lm):
    """A deadline that expires mid-generation cancels at a block boundary:
    the future fails with DeadlineExceeded and lane/pages free."""
    from tpulab.core.deadline import DeadlineExceeded
    cb = _batcher(lm, 8, lanes=1)
    try:
        p = np.random.default_rng(13).integers(0, 64, (4,), np.int32)
        fut = cb.submit(p, 500 // 10, deadline=0.001)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=120)
        deadline = _time.monotonic() + 10
        while (_time.monotonic() < deadline
               and cb.pool.free_pages != cb.pool.n_pages - 1):
            _time.sleep(0.01)
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    finally:
        cb.shutdown()


@pytest.mark.parametrize("ragged", [False, True], ids=["legacy", "ragged"])
def test_a_lane_admitted_beside_a_running_chain_is_not_starved(lm, ragged):
    """Blocks are dispatched ahead from the device-resident carry for as
    long as the lane set is stable; a request admitted meanwhile is in no
    block of that chain.  It must get its steps as soon as its prompt is
    in, not when a lane of the chain completes: here the only other lane
    runs 160 steps, and the late request's 4 tokens must not wait for
    them."""
    done = []
    cb = _batcher(lm, 8, max_len=256, ragged=ragged)
    try:
        started = _time.monotonic()
        long_run = cb.submit([3, 14, 15, 9, 2], 160)
        long_run.add_done_callback(lambda f: done.append("long"))
        while cb.tokens_generated < 20:          # the chain is running
            assert _time.monotonic() - started < 120
            _time.sleep(0.001)
        late = cb.submit([2, 7, 1, 8], 4)
        late.add_done_callback(lambda f: done.append("late"))
        assert len(late.result(timeout=120)) == 4
        assert len(long_run.result(timeout=120)) == 160
        assert done == ["late", "long"]
    finally:
        cb.shutdown()
