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

from helpers_engine import TokenGate
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
    """Regression guard against reintroducing per-token host syncs.  A
    greedy request's prompt of 5 tokens rides ONE mixed round (the budget
    is ``max_len / 2`` = 32), which emits the first token; the other
    ``steps - 1`` come out of blocks of K: its dispatches, and its blocking
    fetches, are at most ``1 + ceil((steps - 1) / K)``."""
    cb = _batcher(lm, 8, lanes=1)
    try:
        p = np.random.default_rng(7).integers(0, 64, (5,), np.int32)
        cb.submit(p, 17).result(timeout=120)   # warm compiles
        s0, d0 = cb.decode_host_syncs, cb.decode_dispatches
        r0, tg0 = cb.dispatch_kinds["mixed"], cb.tokens_generated
        out = cb.submit(p, 17).result(timeout=120)
        assert len(out) == 17
        syncs = cb.decode_host_syncs - s0
        budget = 1 + math.ceil((17 - 1) / cb.decode_block)
        assert syncs <= budget, (syncs, budget)
        assert cb.decode_dispatches - d0 <= budget
        assert cb.dispatch_kinds["mixed"] - r0 == 1
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


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_a_lane_admitted_beside_a_running_chain_is_not_starved(lm, use_kernel):
    """Blocks are dispatched ahead from the device-resident carry for as
    long as the lane set is stable; a request admitted meanwhile is in no
    block of that chain.  It must get its steps as soon as its prompt is
    in, not when a lane of the chain completes: here the only other lane
    runs 160 steps, and the late request's 4 tokens must not wait for
    them."""
    done = []
    cb = _batcher(lm, 8, max_len=256, use_kernel=use_kernel)
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


# -- the chain runs one block ahead of the host -----------------------------
# Block N+1 is enqueued from block N's device-resident carry BEFORE block N
# is fetched (ContinuousBatcher._chain_block), unless the host can foresee
# that the lane set changes inside N.  It moves the moment of an enqueue and
# nothing else: no token, no dispatch, no K.

class _Chain:
    """Records, on the scheduler thread, the order in which a batcher
    enqueues and fetches its decode blocks (numbered as enqueued; the
    chain fetches them in that order), checks the page hoard at every
    enqueue, and runs a hook inside a chosen fetch: there the block ahead
    is in flight and the block being fetched is not committed yet."""

    def __init__(self, cb):
        self.cb = cb
        self.events = []          # ("enqueue", n) | ("fetch", n)
        self.ks = []              # K of block n
        self.at_fetch = {}        # n -> callable
        self.at_enqueue = {}      # n -> callable, as block n is enqueued
        self.hoarded = []         # (pages held, pages allowed)
        self.sent_ahead = []      # block n went behind an un-fetched dispatch
        self._consuming = None
        dispatch, consume, note = (cb._dispatch_block, cb._consume_block,
                                   cb._note_moe)

        def dispatch_block(parts, k, jnp, **chained):
            ps = cb.page_size
            for req in cb._active:
                if req is not None and not req.pending_prompt:
                    allowed = (req.length + 2 * k - 1) // ps + 1
                    if len(req.pages) > allowed:
                        self.hoarded.append((len(req.pages), allowed))
            stash = dispatch(parts, k, jnp, **chained)
            stash["n"] = len(self.ks)
            self.events.append(("enqueue", stash["n"]))
            self.ks.append(k)
            self.sent_ahead.append(bool(chained.get("ahead")))
            hook = self.at_enqueue.pop(stash["n"], None)
            if hook is not None:
                hook()
            return stash

        def consume_block(stash, jnp):
            self._consuming = stash["n"]
            try:
                return consume(stash, jnp)
            finally:
                self._consuming = None

        def note_moe(moe, decode):
            n = self._consuming
            if decode and n is not None:
                self.events.append(("fetch", n))
                hook = self.at_fetch.pop(n, None)
                if hook is not None:
                    hook()
            return note(moe, decode)

        cb._dispatch_block, cb._consume_block, cb._note_moe = (
            dispatch_block, consume_block, note_moe)

    def ahead(self):
        """Blocks enqueued before their predecessor was fetched."""
        at = {e: i for i, e in enumerate(self.events)}
        return [n for n in range(1, len(self.ks))
                if at[("enqueue", n)] < at[("fetch", n - 1)]]


def _sub(seed, **kw):
    """Submit options of a stream that tells stale K/V from fresh: the
    tiny model's greedy stream is one token over and over, a seeded
    device-sampled one is not, and its logprobs move with every K/V row
    the step read."""
    return dict(kw, logprobs=True, sampling=SamplingParams(
        temperature=0.9, seed=seed, device=True))


def _reference(lm, cases, **kw):
    """What a ``decode_block=1`` engine (no block, no chain) answers."""
    cb = _batcher(lm, 1, **kw)
    try:
        return [cb.submit(p, s, **sub).result(timeout=120)
                for p, s, sub in cases]
    finally:
        cb.shutdown()


def _same(got, want):
    assert list(got[0]) == list(want[0])
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("steps, blocks, ahead", [
    (41, [8, 8, 8, 8, 8], [1, 2, 3, 4]),   # a steady chain: all but the first
    (36, [8, 8, 8, 8, 4], [1, 2, 3]),      # K changes: a regular plan again
    (20, [8, 8, 4], [1]),
    (17, [8, 8], [1]),                     # block 1 holds the last 8 steps
    (9, [8], []),
    (6, [8], []),                          # the budget ends inside block 0
])
def test_chain_runs_one_block_ahead_up_to_a_foreseen_completion(
        lm, steps, blocks, ahead):
    """In a running chain the enqueue of block N+1 precedes the fetch of
    block N.  A lane whose step budget ends inside N is a completion the
    host can foresee: no block is enqueued past it, so a request of S
    steps makes exactly the blocks it made when every block was enqueued
    after its predecessor's commit (``blocks``: the K of each).  The chain's
    head is the round that carries the prompt (5 tokens: one round, which
    emits the first token): by ``_chain_block``'s rule block 0 goes behind
    it un-fetched wherever the request wants more than one token more (K
    > 1) and its budget does not end in the round, as in every case here.
    So ``ahead_blocks`` counts block 0 and the blocks ``ahead`` lists, and
    the request makes ``len(blocks) + 1`` dispatches and fetches."""
    p = np.random.default_rng(7).integers(0, 64, (5,), np.int32)
    (want,) = _reference(lm, [(p, steps, _sub(3))], lanes=1)
    cb = _batcher(lm, 8, lanes=1)
    chain = _Chain(cb)
    try:
        got = cb.submit(p, steps, **_sub(3)).result(timeout=120)
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    _same(got, want)
    assert len(got[0]) == steps
    assert chain.ks == blocks
    assert chain.ahead() == ahead
    assert chain.sent_ahead[0]            # behind the un-fetched round
    assert state["ahead_blocks"] == cb.ahead_blocks == len(ahead) + 1
    assert state["kinds"] == {"mixed": 1, "decode": len(blocks), "verify": 0}
    assert state["decode_dispatches"] == len(blocks) + 1
    assert state["decode_host_syncs"] == len(blocks) + 1
    assert not chain.hoarded
    assert cb.pool.free_pages == cb.pool.n_pages - 1


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_chain_ahead_parity_with_a_page_crossed_by_every_block(
        lm, use_kernel, sampled):
    """Tokens and logprobs of a chain that runs ahead are those of single
    steps, with K equal to the page size so that every block enqueued
    ahead reserves and crosses into a new page from a committed length
    that lags it by a block (two lanes, different lengths)."""
    rng = np.random.default_rng(17)
    cases = [(rng.integers(0, 64, (n,), np.int32), s,
              _sub(77 + n) if sampled else {"logprobs": True})
             for n, s in ((5, 45), (11, 38))]
    want = _reference(lm, cases, use_kernel=use_kernel)
    cb = _batcher(lm, 8, use_kernel=use_kernel)
    chain = _Chain(cb)
    try:
        futs = [cb.submit(p, s, **sub) for p, s, sub in cases]
        got = [f.result(timeout=120) for f in futs]
    finally:
        cb.shutdown()
    for g, w in zip(got, want):
        _same(g, w)
    # (a chain's first block goes behind the round that brought its
    # lanes' prompts in, not yet fetched either: at most one a request)
    assert len(chain.ahead()) >= 3
    assert cb.ahead_blocks == sum(chain.sent_ahead)
    assert set(chain.ahead()) <= {n for n, a in enumerate(chain.sent_ahead)
                                  if a}
    assert cb.ahead_blocks - len(chain.ahead()) <= 2
    assert not chain.hoarded
    assert cb.pool.free_pages == cb.pool.n_pages - 1


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_stop_token_with_the_block_ahead_in_flight(lm, use_kernel):
    """A stop token is a completion the host cannot foresee: it lands in
    block N with block N+1 already enqueued.  The stream ends on the stop
    token, the dead block emits nothing (its lane is dead in the carry,
    its writes go to the scratch page), every page comes home, and the
    request admitted to that lane while the dead block is still in flight
    reads no stale K/V."""
    rng = np.random.default_rng(31)
    pa = rng.integers(0, 64, (6,), np.int32)
    pb = rng.integers(0, 64, (9,), np.int32)
    ref_a, ref_b = _reference(lm, [(pa, 60, _sub(5)), (pb, 20, _sub(6))],
                              lanes=1, max_len=128, use_kernel=use_kernel)
    # a token first seen in the stream at index >= 18: in block 2 or later
    # (token i comes out of block (i - 1) // 8)
    toks = list(ref_a[0])
    idx = next(i for i in range(18, 52) if toks[i] not in toks[:i])
    n_stop = (idx - 1) // 8
    streamed = []
    cb = _batcher(lm, 8, lanes=1, max_len=128, use_kernel=use_kernel)
    chain = _Chain(cb)
    try:
        fa = cb.submit(pa, 60, stop_tokens=[toks[idx]], **_sub(5))
        fb = cb.submit(pb, 20, **_sub(
            6, on_token=lambda tok, i, lp: streamed.append(tok)))
        got_a = fa.result(timeout=120)
        got_b = fb.result(timeout=120)
    finally:
        cb.shutdown()
    _same(got_a, (toks[:idx + 1], ref_a[1][:idx + 1]))
    assert n_stop + 1 in chain.ahead()     # the dead block was in flight,
    assert chain.ks[:n_stop + 2] == [8] * (n_stop + 2)
    assert chain.ks[n_stop + 2] != 8 or n_stop + 2 not in chain.ahead()
    _same(got_b, ref_b)                    # and no block followed it
    assert streamed == list(ref_b[0])
    assert cb.pool.free_pages == cb.pool.n_pages - 1


@pytest.mark.parametrize("event", ["cancel", "deadline", "preempt",
                                   "preempt_kv_offload"])
def test_host_event_with_the_block_ahead_in_flight(lm, event):
    """Cancel, deadline expiry and preemption land while block N is being
    fetched: block N+1 is already on the device's queue.  A cancelled
    stream emits nothing more (not even block N), an expired one ends at
    the sweep after N, a preempted one resumes exactly (its K/V swapped
    out of the pool block N+1 returned, or prefilled again), and the lane's
    next owner reads no stale K/V."""
    from tpulab.core.deadline import DeadlineExceeded
    rng = np.random.default_rng(41)
    p_low = rng.integers(0, 64, (6,), np.int32)
    p_next = rng.integers(0, 64, (5,), np.int32)
    ref_low, ref_next = _reference(
        lm, [(p_low, 60, _sub(8)), (p_next, 12, _sub(9))], lanes=1,
        max_len=128)
    kw = {"kv_offload": 32 << 20} if event == "preempt_kv_offload" else {}
    cb = _batcher(lm, 8, lanes=1, max_len=128, **kw)
    chain = _Chain(cb)
    streamed, others = [], []
    try:
        # a streaming consumer: blocks of K = 2; tokens 2n+1, 2n+2 are
        # block n's, so blocks 0..4 commit tokens 0..10
        fut = cb.submit(p_low, 60, **_sub(
            8, on_token=lambda tok, i, lp: streamed.append((i, tok))))

        def act():
            assert cb._pending_block is not None   # block 6 is in flight
            if event == "cancel":
                cb.cancel(fut)
            elif event == "deadline":
                cb._requests[fut].deadline = _time.monotonic() - 1.0
            else:
                others.append(cb.submit(p_next, 12, priority=10, **_sub(9)))

        chain.at_fetch[5] = act
        if event == "cancel":
            with pytest.raises(Exception):
                fut.result(timeout=120)
            assert [i for i, _t in streamed] == list(range(11))
        elif event == "deadline":
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=120)
            assert [i for i, _t in streamed] == list(range(13))
        else:
            _same(fut.result(timeout=120), ref_low)
            _same(others[0].result(timeout=120), ref_next)
            assert cb.preemptions == 1
            assert [i for i, _t in streamed] == list(range(60))
            if kw:
                assert (cb.kv_offload.swap_outs >= 1
                        and cb.kv_offload.swap_ins >= 1)
        assert [t for _i, t in streamed] == list(
            ref_low[0][:len(streamed)])
        assert 6 in chain.ahead() and not chain.at_fetch
        # the lane's next owner, on pages the discarded block wrote
        _same(cb.submit(p_next, 12, **_sub(9)).result(timeout=120), ref_next)
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


@pytest.mark.parametrize("free, ks, ahead", [
    (2, [8, 2, 8, 8, 8, 8], [3, 4, 5]),    # block 0 fits, block 1 does not
    (3, [8, 8, 2, 8, 8, 8], [1, 4, 5]),    # block 1 ahead, block 2 refused
    (4, [8, 8, 8, 2, 8, 8], [1, 2, 5]),
])
def test_chain_ahead_under_page_pressure_keeps_k_and_bounds_the_hoard(
        lm, free, ks, ahead):
    """Where the pool cannot cover the block ahead the chain does not run
    ahead for it and never shrinks K for it: the block is planned after
    its predecessor's commit, as it always was (that regular plan may
    shrink K, here to the 2 steps the lane's last page still holds).  The
    lane is not starved once pages return, every token is the
    reference's, and a lane never holds pages past ``length + 2K``.  Block
    0 goes behind the un-fetched round that carried the prompt (6 tokens
    in the admission page; its 8 rows end in the one page more that every
    case leaves free), so ``ahead_blocks`` counts it beside ``ahead``."""
    p = np.random.default_rng(11).integers(0, 64, (6,), np.int32)
    (want,) = _reference(lm, [(p, 41, _sub(21))], lanes=1)
    cb = _batcher(lm, 8, lanes=1)
    chain = _Chain(cb)
    try:
        # all but ``free`` pages are somebody else's until the short block
        # (the one K shrank for) is being fetched
        held = [cb.pool.allocate_page()
                for _ in range(cb.pool.free_pages - free)]
        chain.at_fetch[ks.index(2)] = lambda: cb.pool.release_pages(held)
        got = cb.submit(p, 41, **_sub(21)).result(timeout=120)
    finally:
        cb.shutdown()
    _same(got, want)
    assert chain.ks == ks and chain.ahead() == ahead
    assert chain.sent_ahead[0] and cb.ahead_blocks == len(ahead) + 1
    assert not chain.hoarded
    assert cb.pool.free_pages == cb.pool.n_pages - 1


# -- the mixed round is a member of the chain --------------------------------
# While a prompt waits every dispatch is a round, and every round carries
# every decoding lane from its predecessor's device carry
# (ContinuousBatcher._chain_block): a round goes behind an un-fetched block
# or round, a block behind an un-fetched round.

class _Dispatches:
    """Every decode block and mixed round a batcher enqueues, numbered as
    enqueued (``kinds``, ``stashes``), and the order in which it enqueues
    them and fetches their results (``cb._fetch`` of the dispatch's one
    result array), recorded on the scheduler thread."""

    def __init__(self, cb):
        self.kinds, self.stashes = [], []
        self.events = []          # ("enqueue", n) | ("fetch", n)
        self.at_enqueue = {}      # n -> callable, as dispatch n is enqueued
        by_out = {}
        block, round_, fetch = (cb._dispatch_block, cb._dispatch_round,
                                cb._fetch)

        def note(stash):
            by_out[id(stash["out"])] = len(self.kinds)
            self.events.append(("enqueue", len(self.kinds)))
            self.kinds.append(stash["kind"])
            self.stashes.append(stash)
            hook = self.at_enqueue.pop(len(self.kinds) - 1, None)
            if hook is not None:
                hook()
            return stash

        def fetched(dev):
            if id(dev) in by_out:
                self.events.append(("fetch", by_out[id(dev)]))
            return fetch(dev)
        cb._dispatch_block = lambda *a, **kw: note(block(*a, **kw))
        cb._dispatch_round = lambda *a, **kw: note(round_(*a, **kw))
        cb._fetch = fetched

    def last_fetched(self):
        return next(n for what, n in reversed(self.events)
                    if what == "fetch")

    def behind(self, n):
        """Dispatch ``n`` was enqueued before dispatch ``n - 1`` was
        fetched."""
        at = {e: i for i, e in enumerate(self.events)}
        return n > 0 and at[("enqueue", n)] < at[("fetch", n - 1)]

    def links(self):
        """``(kind of n - 1, kind of n)`` of every dispatch that went
        behind an un-fetched predecessor."""
        return {(self.kinds[n - 1], self.kinds[n])
                for n in range(1, len(self.kinds)) if self.behind(n)}


def test_a_request_that_arrives_during_a_dispatch_does_not_hold_the_chain(lm):
    """A closed-loop caller's next request arrives while the scheduler
    enqueues a chain's first dispatch (the round that carries the first
    request's prompt), after the admission at the top of its pass.  Left
    queued it would wait for the round's fetch and commit to take its
    lane, and read meanwhile as queue pressure to the K policy.  The
    decision before the fetch (``_chain_block``) admits it first, and a
    prompt that waits makes the successor a round: dispatch 1 carries the
    late prompt and the first lane's decode row from the carry, enqueued
    before round 0 is fetched, and the block behind it takes both lanes."""
    rng = np.random.default_rng(51)
    pa = rng.integers(0, 64, (6,), np.int32)
    pb = rng.integers(0, 64, (7,), np.int32)
    ref_a, ref_b = _reference(lm, [(pa, 30, _sub(12)), (pb, 10, _sub(13))],
                              lanes=1, max_len=128)
    cb = _batcher(lm, 8, lanes=2, max_len=128)
    spy = _Dispatches(cb)
    late = []
    try:
        spy.at_enqueue[0] = lambda: late.append(
            cb.submit(pb, 10, **_sub(13)))
        fa = cb.submit(pa, 30, **_sub(12, on_token=lambda tok, i, lp: None))
        _same(fa.result(timeout=120), ref_a)
        _same(late[0].result(timeout=120), ref_b)
    finally:
        cb.shutdown()
    assert spy.kinds[:3] == ["round", "round", "block"]
    assert spy.behind(1) and spy.behind(2)
    assert len(spy.stashes[1]["decodes"]) == len(spy.stashes[1]["firsts"]) == 1
    assert len(spy.stashes[2]["lane_reqs"]) == 2
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def _beside(lm, first, second, at=5, reference=True, **kw):
    """``first`` = (prompt, steps, submit options) streams on one lane of an
    engine of two (pages of 8, a round budget of 8); as its token
    ``at`` is emitted, from the scheduler's own thread, ``second`` is
    submitted: its prompt comes in beside the running chain.  Returns
    ``(cb, spy, [first's result, second's result], tokens by dispatch)``;
    the caller shuts ``cb`` down."""
    cb = _batcher(lm, 8, lanes=2, max_len=160, prefill_chunk=8,
                  **kw)
    spy = _Dispatches(cb)
    futs, emitted_by = [], {}
    (pa, sa, sub_a), (pb, sb, sub_b) = first, second
    hook_a = sub_a.pop("on_token", None)

    def on_token(tok, i, *lp):
        emitted_by[i] = spy.last_fetched() if spy.events else None
        if hook_a is not None:
            hook_a(tok, i, *lp)
        if i == at:
            futs.append(cb.submit(pb, sb, **sub_b))
    futs.append(cb.submit(pa, sa, on_token=on_token, **sub_a))
    return cb, spy, futs, emitted_by


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_rounds_and_blocks_chain_through_one_carry(lm, sampled):
    """A prompt of four rounds arrives beside a streaming lane: the first
    of its rounds goes behind the un-fetched block of the running chain,
    each next round behind its un-fetched predecessor, with the streaming
    lane a decode row from the carry; the block that follows the prompt's
    LAST round goes behind that round un-fetched, and carries the new
    lane from the round's carry: no ``joiner`` break.  Every stream is the
    ``decode_block=1`` engine's, which fetches every dispatch."""
    rng = np.random.default_rng(61)
    pa = rng.integers(0, 64, (5,), np.int32)
    pb = rng.integers(0, 64, (30,), np.int32)
    sub = (lambda s: _sub(s)) if sampled else (lambda s: {"logprobs": True})
    ref_a, ref_b = _reference(lm, [(pa, 40, sub(21)), (pb, 12, sub(22))],
                              lanes=1, max_len=160)
    cb, spy, futs, _by = _beside(lm, (pa, 40, sub(21)), (pb, 12, sub(22)))
    try:
        got_a = futs[0].result(timeout=120)
        got_b = futs[1].result(timeout=120)
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    _same(got_a, ref_a)
    _same(got_b, ref_b)
    assert spy.links() >= {("block", "round"), ("round", "round"),
                           ("round", "block")}
    rounds = [n for n, kind in enumerate(spy.kinds) if kind == "round"]
    # the second prompt: four rounds of 8, 8, 8 and 6 tokens, all of them
    # enqueued ahead, the streaming lane a decode row of each
    late = rounds[-4:]
    assert late == list(range(late[0], late[0] + 4))
    assert all(spy.behind(n) for n in late)
    assert all(len(spy.stashes[n]["decodes"]) == 1 for n in late)
    # its prompt ends in the last of them, un-fetched when the block
    # behind it takes both lanes from its carry
    last = spy.stashes[late[-1]]
    (lane_b, req_b, _resumed), = last["firsts"]
    after = spy.stashes[late[-1] + 1]
    assert after["kind"] == "block" and spy.behind(late[-1] + 1)
    assert after["lane_reqs"][lane_b] is req_b and len(after["lane_reqs"]) == 2
    assert state["chain"]["breaks"]["joiner"] == 0
    assert state["ahead_rounds"] >= 4 and state["rounds_after_round"] >= 3
    assert state["mixed_decode_rows"] >= 4
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_stop_token_inside_an_unfetched_round(lm):
    """A stop token drawn by a decode row of round N, with round N+1
    already enqueued: the stream ends on the stop token, the lane's row in
    N+1 is dead through the carry (nothing is emitted, nothing of its
    pages moves), every page comes home and the lane's next owner reads
    no stale K/V."""
    rng = np.random.default_rng(67)
    pa = rng.integers(0, 64, (6,), np.int32)
    pb = rng.integers(0, 64, (80,), np.int32)        # ten rounds of 8
    pc = rng.integers(0, 64, (9,), np.int32)
    ref_a, ref_b, ref_c = _reference(
        lm, [(pa, 60, _sub(5)), (pb, 6, _sub(6)), (pc, 10, _sub(7))],
        lanes=1, max_len=160)
    # tokens 9 .. 18 of the first stream come out of the second prompt's
    # rounds, one a round: stop on one first seen there
    toks = list(ref_a[0])
    idx = next(i for i in range(11, 17) if toks[i] not in toks[:i])
    cb, spy, futs, by = _beside(
        lm, (pa, 60, dict(_sub(5), stop_tokens=[toks[idx]])),
        (pb, 6, _sub(6)))
    try:
        got_a = futs[0].result(timeout=120)
        got_b = futs[1].result(timeout=120)
        got_c = cb.submit(pc, 10, **_sub(7)).result(timeout=120)
        breaks = dict(cb.chain_breaks)
    finally:
        cb.shutdown()
    _same(got_a, (toks[:idx + 1], ref_a[1][:idx + 1]))
    _same(got_b, ref_b)
    _same(got_c, ref_c)
    n = by[idx]                      # the dispatch that drew the stop token
    assert spy.kinds[n] == spy.kinds[n + 1] == "round" and spy.behind(n + 1)
    (lane_a, _req), = spy.stashes[n]["decodes"]
    # the round behind it held a row for the lane: dead, and the chain
    # broke at ITS consume, where the host had seen the lane released
    assert [lane for lane, _ in spy.stashes[n + 1]["decodes"]] == [lane_a]
    assert not spy.behind(n + 2) and breaks["released"] >= 1
    assert max(by) == idx
    assert cb.pool.free_pages == cb.pool.n_pages - 1


@pytest.mark.parametrize("publish", [False, True],
                         ids=["kv_publish-off", "kv_publish-on"])
def test_a_first_prompts_end_breaks_the_chain_only_where_it_publishes(
        lm, publish):
    """With ``kv_publish`` the round in which a first prompt ends is
    fetched before anything goes behind it (the snapshot is a gather on
    the pages as that round left them), counted under the cause ``host``.
    Without it (every cell) that round takes no such branch: two greedy
    lanes, the second prompt arriving while the first is held at a token,
    add nothing to ``host``, and each prompt's last round has its
    successor enqueued before its fetch."""
    rng = np.random.default_rng(73)
    pa = rng.integers(0, 64, (6,), np.int32)
    pb = rng.integers(0, 64, (7,), np.int32)
    cb = _batcher(lm, 8, lanes=2, max_len=128, kv_offload=32 << 20,
                  kv_publish=publish)
    spy = _Dispatches(cb)
    try:
        held = TokenGate(4)
        fa = cb.submit(pa, 30, on_token=held)
        assert held.wait(timeout=60)
        fb = cb.submit(pb, 10)
        held.release()
        assert len(fa.result(timeout=120)) == 30
        assert len(fb.result(timeout=120)) == 10
        breaks = cb.debug_state()["dispatch"]["chain"]["breaks"]
    finally:
        cb.shutdown()
    ends = [n for n, stash in enumerate(spy.stashes)
            if stash["kind"] == "round" and stash["firsts"]]
    assert len(ends) == 2
    assert breaks["host"] == (2 if publish else 0)
    assert [spy.behind(n + 1) for n in ends] == [not publish] * 2
    assert cb.kv_publishes == (2 if publish else 0)
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def _host_sampled(seed):
    return dict(sampling=SamplingParams(temperature=0.8, top_k=4, seed=seed))


@pytest.mark.parametrize("cause", ["completion", "released", "host"])
def test_a_round_s_chain_breaks_with_its_cause(lm, cause):
    """Where the host can foresee that the lane set changes inside a round
    (a step budget that ends in it), where it changed (a cancel) and where
    a lane's pick is made on the host (``top_k``: its token is not in the
    carry), the round is fetched before its successor is planned, counted
    under its cause, and the streams are the reference's."""
    rng = np.random.default_rng(71)
    pa = rng.integers(0, 64, (6,), np.int32)
    pb = rng.integers(0, 64, (80,), np.int32)
    steps = 14 if cause == "completion" else 40
    # (a host-sampled request's PRNG lives in its options: one each a run)
    options = (lambda: _host_sampled(3)) if cause == "host" else (
        lambda: _sub(5))
    ref_a, ref_b = _reference(lm, [(pa, steps, options()), (pb, 6, _sub(6))],
                              lanes=1, max_len=160)
    sub_a = options()
    streamed = []
    if cause == "released":
        sub_a["on_token"] = lambda tok, i, lp: (
            streamed.append(tok), i == 12 and cb.cancel(futs[0]))
    cb, spy, futs, by = _beside(lm, (pa, steps, sub_a), (pb, 6, _sub(6)))
    try:
        if cause == "released":
            with pytest.raises(Exception):
                futs[0].result(timeout=120)
            assert streamed == list(ref_a[0][:13])
        elif cause == "host":
            assert list(futs[0].result(timeout=120)) == list(ref_a)
        else:
            _same(futs[0].result(timeout=120), ref_a)
        _same(futs[1].result(timeout=120), ref_b)
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    breaks = state["chain"]["breaks"]
    assert breaks[cause] >= 1
    rounds = [n for n, kind in enumerate(spy.kinds) if kind == "round"]
    if cause == "completion":
        # tokens 9 .. 13 came out of rounds; the round that holds the last
        # one got no successor before its fetch
        n = by[steps - 1]
        assert spy.kinds[n] == "round" and not spy.behind(n + 1)
        assert spy.behind(n)
    elif cause == "host":
        # every round that carried the host-sampled lane was fetched first
        carried = [n for n in rounds if spy.stashes[n]["host_lanes"]]
        assert len(carried) >= 5 and breaks["host"] >= len(carried) - 1
        assert not any(spy.behind(n + 1) for n in carried
                       if n + 1 < len(spy.kinds))
    assert cb.pool.free_pages == cb.pool.n_pages - 1
