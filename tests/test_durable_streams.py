"""Durable token streams (ISSUE 9 tentpole, docs/ROBUSTNESS.md "Stream
failover semantics"): fast IN-PROCESS mid-stream-death and stall coverage
over real loopback gRPC replicas serving paged engines.

The contracts test-enforced here:

- a replica killed mid-stream (chaos ``rpc.stream=error``) yields ONE
  uninterrupted, bit-exact token stream for greedy, device-sampled and
  logprobs requests, with ZERO per-token re-decode dispatches for the
  already-delivered prefix on the resume path — the survivor pays one
  chunked prefill (its generated-token count is exactly the remainder);
- host-sampled requests (draw-order PRNG, does not survive the hop) fall
  back to today's full replay with identical output;
- a STALLED (not dead) replica (chaos ``rpc.stream=drop``) fails over
  within the inter-token bound, not the 300 s activity timeout, counted
  as the distinct ``stalled`` evidence class;
- hedged first token: a primary with no first token within the hedge
  delay loses the race to one duplicate attempt, first-writer-wins, the
  loser cancelled through the existing cancel path.

Before this file the only mid-stream kill coverage was the one slow
subprocess test in tests/test_chaos.py.
"""

import time

import numpy as np
import pytest

import tpulab
from tpulab import chaos
from tpulab.engine.paged import SamplingParams
from tpulab.models.mnist import make_mnist

pytestmark = pytest.mark.chaos

PROMPT = None  # set by the fixture (stable across tests)
STEPS = 16


def _lm_params():
    from tpulab.models.transformer import init_transformer_params
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)  # seed=0 default


def _serve_paged(params):
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    cb = ContinuousBatcher(params, n_heads=2, n_layers=2, lanes=2,
                           max_len=64, page_size=8,
                           compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    return mgr, cb


@pytest.fixture(scope="module")
def pair():
    """Two identical-weights paged replicas, every jit path pre-warmed
    (greedy, device-sampled, logprobs, and the resume prefill bucket) so
    tight stall bounds never race compilation."""
    global PROMPT
    params = _lm_params()
    mgr_a, cb_a = _serve_paged(params)
    mgr_b, cb_b = _serve_paged(params)
    rng = np.random.default_rng(42)
    PROMPT = rng.integers(0, 64, (10,), np.int32)  # pow2 prefill bucket 16:
    #                       resume prompts (10 + delivered <= 16) share it
    for cb in (cb_a, cb_b):
        # streaming consumers drop the adaptive block to K<=2 — a
        # DIFFERENT compiled scan than batch-style submits, so warm with
        # an on_token hook or the tight stall bounds race compilation
        cb.submit(PROMPT, 4,
                  on_token=lambda *a: None).result(timeout=300)
        cb.submit(PROMPT, 4, sampling=SamplingParams(
            temperature=0.9, seed=7, device=True),
            on_token=lambda *a: None).result(timeout=300)
        cb.submit(PROMPT, 4, logprobs=True,
                  on_token=lambda *a: None).result(timeout=300)
    yield (mgr_a, cb_a), (mgr_b, cb_b)
    for m in (mgr_a, mgr_b):
        try:
            m.shutdown()
        except Exception:
            pass
    for cb in (cb_a, cb_b):
        try:
            cb.shutdown()
        except Exception:
            pass


def _set(pair, **kw):
    from tpulab.rpc.replica import GenerationReplicaSet
    (mgr_a, _), (mgr_b, _) = pair
    addrs = [f"127.0.0.1:{m.server.bound_port}" for m in (mgr_a, mgr_b)]
    return GenerationReplicaSet(addrs, "lm", **kw)


def _snap(cb):
    return (cb.tokens_generated, cb.dispatch_kinds["mixed"])


# ------------------------------------------------ resume bit-exactness ----
def test_resume_greedy_mid_stream_kill_bit_exact_zero_redecode(pair):
    """Chaos-killed stream at token 4: the survivor RESUMES — one
    uninterrupted bit-exact greedy stream, zero replayed tokens, and the
    surviving engine decodes ONLY the remainder (its generated-token
    delta is exactly steps - delivered: the delivered prefix rode one
    chunked prefill, never per-token re-decode dispatches)."""
    (_, cb_a), (_, cb_b) = pair
    engines = [cb_a, cb_b]
    expected = [int(t) for t in
                cb_a.submit(PROMPT, STEPS).result(timeout=300)]
    rs = _set(pair)
    try:
        kill_at = 4
        snaps = [_snap(cb) for cb in engines]
        with chaos.inject(f"rpc.stream=error@{kill_at}+1") as sched:
            got = [int(t) for t in rs.generate(PROMPT, STEPS)]
            assert sched.fired("rpc.stream") == 1
        assert got == expected, (got, expected)
        assert rs.resumes == 1 and rs.tokens_replayed == 0
        assert rs.resume_fallbacks == 0 and sum(rs.served) == 1
        winner = rs.served.index(1)
        toks1, pre1 = _snap(engines[winner])
        toks0, pre0 = snaps[winner]
        # the acceptance contract: the resume admission generated exactly
        # the remaining tokens (first via the prefill pick, the rest via
        # decode) after exactly one fresh chunked prefill
        assert toks1 - toks0 == STEPS - kill_at, (toks1 - toks0, STEPS,
                                                  kill_at)
        assert pre1 - pre0 == 1
    finally:
        rs.close()


def test_resume_device_sampled_bit_exact(pair):
    """Device sampling keys its Gumbel stream by (seed, position), so the
    resumed continuation is bit-exact across the replica hop."""
    (_, cb_a), _ = pair
    sp = SamplingParams(temperature=0.9, seed=777, device=True)
    expected = [int(t) for t in
                cb_a.submit(PROMPT, STEPS, sampling=sp).result(timeout=300)]
    assert len(set(expected)) > 1, "degenerate fixture: sampling is moot"
    rs = _set(pair)
    try:
        with chaos.inject("rpc.stream=error@5+1"):
            got = [int(t) for t in rs.generate(
                PROMPT, STEPS, temperature=0.9, device_sampling=True,
                seed=777)]
        assert got == expected, (got, expected)
        assert rs.resumes == 1 and rs.tokens_replayed == 0
    finally:
        rs.close()


def test_resume_logprobs_bit_exact(pair):
    """logprobs=True through a mid-stream kill: tokens exact, the
    on-device f32 log-softmax stream continues on the survivor (allclose
    like the K-parity tests: program shapes may fuse differently)."""
    (_, cb_a), _ = pair
    toks_ref, lps_ref = cb_a.submit(PROMPT, STEPS,
                                    logprobs=True).result(timeout=300)
    rs = _set(pair)
    try:
        with chaos.inject("rpc.stream=error@4+1"):
            got = list(rs.generate(PROMPT, STEPS, return_logprobs=True))
        assert [int(t) for t, _ in got] == [int(t) for t in toks_ref]
        np.testing.assert_allclose([lp for _, lp in got],
                                   np.asarray(lps_ref, np.float32),
                                   rtol=1e-4, atol=1e-5)
        assert rs.resumes == 1 and rs.tokens_replayed == 0
    finally:
        rs.close()


def test_host_sampled_falls_back_to_full_replay_identical_output(pair):
    """Host-sampled streams are keyed by PRNG draw order — resume cannot
    survive the hop, so the client degrades to today's full replay:
    identical output, delivered tokens re-received and skipped."""
    (_, cb_a), _ = pair
    sp = SamplingParams(temperature=0.9, seed=123)  # host PRNG
    expected = [int(t) for t in
                cb_a.submit(PROMPT, STEPS, sampling=sp).result(timeout=300)]
    rs = _set(pair)
    try:
        kill_at = 3
        with chaos.inject(f"rpc.stream=error@{kill_at}+1"):
            got = [int(t) for t in rs.generate(PROMPT, STEPS,
                                               temperature=0.9, seed=123)]
        assert got == expected, (got, expected)
        assert rs.resumes == 0                    # never attempted
        assert rs.tokens_replayed == kill_at      # the waste resume removes
    finally:
        rs.close()


def test_server_rejects_invalid_resume_forms(pair):
    """The server-side safety net: a host-sampled resume (or a resume
    with nothing left to generate) is a deterministic INVALID_ARGUMENT
    rejection, never silently-divergent tokens."""
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          GenerationRejected,
                                          RemoteInferenceManager)
    (mgr_a, _), _ = pair
    remote = RemoteInferenceManager(f"127.0.0.1:{mgr_a.server.bound_port}")
    try:
        client = GenerateStreamClient(remote, "lm")
        with pytest.raises(GenerationRejected) as ei:
            list(client.generate(list(PROMPT) + [1, 2], 8, temperature=0.7,
                                 seed=3, resume_length=2))
        assert not ei.value.retryable
        assert "greedy or device sampling" in str(ei.value)
        with pytest.raises(GenerationRejected) as ei:
            list(client.generate(list(PROMPT) + [1, 2, 3], 3,
                                 resume_length=3))
        assert not ei.value.retryable
    finally:
        remote.close()


# ------------------------------------------------------ stall watchdog ----
def test_stalled_stream_fails_over_within_inter_token_bound(pair):
    """chaos ``rpc.stream=drop``: the replica STOPS emitting but stays
    open — only the inter-token watchdog can catch it.  The stream fails
    over (with resume) within seconds, not the 300 s activity timeout,
    and the stall is counted as its own evidence class."""
    (_, cb_a), _ = pair
    expected = [int(t) for t in
                cb_a.submit(PROMPT, STEPS).result(timeout=300)]
    rs = _set(pair, inter_token_timeout_s=1.0)
    try:
        t0 = time.perf_counter()
        with chaos.inject("rpc.stream=drop@3+1"):
            got = [int(t) for t in rs.generate(PROMPT, STEPS)]
        wall = time.perf_counter() - t0
        assert got == expected, (got, expected)
        assert rs.stalls == 1
        assert rs.resumes == 1 and rs.tokens_replayed == 0
        assert wall < 30.0, f"stall failover took {wall:.1f}s"
    finally:
        rs.close()


def test_stall_watchdog_raises_stream_stalled(pair):
    """The raw client bound: no progress within inter_token_timeout
    raises StreamStalled (phase-tagged), a TimeoutError subclass —
    generic timeout handling survives, routers see the distinct class."""
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager,
                                          StreamStalled)
    (mgr_a, _), _ = pair
    remote = RemoteInferenceManager(f"127.0.0.1:{mgr_a.server.bound_port}")
    try:
        client = GenerateStreamClient(remote, "lm")
        with chaos.inject("rpc.stream=drop@2+1"):
            gen = client.generate(PROMPT, 12, inter_token_timeout=0.8)
            t0 = time.perf_counter()
            with pytest.raises(StreamStalled) as ei:
                list(gen)
        assert ei.value.phase == "inter_token"
        assert isinstance(ei.value, TimeoutError)
        assert time.perf_counter() - t0 < 20.0
    finally:
        remote.close()


# -------------------------------------------------- hedged first token ----
def test_hedged_first_token_first_writer_wins(pair):
    """The primary's emit path wedges before the first token; after the
    hedge delay one duplicate attempt launches on the other replica and
    wins the race — bit-exact stream, loser cancelled (its lane frees
    through the existing cancel path)."""
    (_, cb_a), (_, cb_b) = pair
    engines = [cb_a, cb_b]
    expected = [int(t) for t in
                cb_a.submit(PROMPT, STEPS).result(timeout=300)]
    rs = _set(pair, hedge_delay_s=0.3)
    try:
        with chaos.inject("rpc.stream=drop@0+1"):
            got = [int(t) for t in rs.generate(PROMPT, STEPS)]
        assert got == expected, (got, expected)
        assert rs.hedges == 1 and rs.hedge_wins == 1
        assert sum(rs.served) == 1
        # the cancelled loser's lane frees (cancel path, not a leak)
        deadline = time.monotonic() + 15
        while (time.monotonic() < deadline
               and any(cb.active_lanes for cb in engines)):
            time.sleep(0.02)
        assert all(cb.active_lanes == 0 for cb in engines)
    finally:
        rs.close()


def test_hedge_eligibility_rules(pair):
    """Hedging is opt-in and self-limiting: never for host-sampled
    requests, and skipped while ANY replica is in overload backoff so a
    hedge can never amplify the overload it would ride into."""
    rs = _set(pair, hedge_delay_s=0.1)
    try:
        assert rs._hedge_eligible({}) is True
        assert rs._hedge_eligible({"temperature": 0.5}) is False
        assert rs._hedge_eligible(
            {"temperature": 0.5, "device_sampling": True}) is True
        rs._backoff_until[1] = time.monotonic() + 60  # overload backoff
        assert rs._hedge_eligible({}) is False
    finally:
        rs.close()


def test_hedge_default_off(pair):
    """No hedge_delay_s: generate never races a duplicate attempt."""
    rs = _set(pair)
    try:
        assert rs._hedge_eligible({}) is False
        got = [int(t) for t in rs.generate(PROMPT, 6)]
        assert len(got) == 6 and rs.hedges == 0
    finally:
        rs.close()


def test_hedge_lands_on_affinity_second_rank(pair):
    """PR 13 regression: hedging consults the affinity ranking.  With
    affinity on, the primary is the prompt's rendezvous home and the
    duplicate launches on the SECOND-ranked replica — never a random
    spare, never the primary's own replica."""
    (_, cb_a), _ = pair
    expected = [int(t) for t in
                cb_a.submit(PROMPT, STEPS).result(timeout=300)]
    rs = _set(pair, hedge_delay_s=0.3, prefix_affinity=True,
              affinity_tokens=8)
    try:
        home = rs._preferred(list(PROMPT))
        second = 1 - home
        # the hedge's pick IS the affinity second rank
        picked = rs._hedge_pick(list(PROMPT), frozenset({home}))
        assert picked == second
        with rs._lock:
            rs._inflight[picked] -= 1  # undo the pick's hold
        # e2e: primary (the home) wedges before its first token; the
        # duplicate wins from the second rank, bit-exact
        with chaos.inject("rpc.stream=drop@0+1"):
            got = [int(t) for t in rs.generate(PROMPT, STEPS)]
        assert got == expected, (got, expected)
        assert rs.hedges == 1 and rs.hedge_wins == 1
        assert rs.served[second] == 1 and rs.served[home] == 0
    finally:
        rs.close()


def test_hedge_ineligible_without_distinct_second_replica(pair):
    """PR 13 regression: _hedge_eligible consults routing state, not
    raw set size — a fleet whose other replica is draining must not
    hedge (the duplicate could only re-land on the primary's replica),
    and _hedge_pick never falls back onto an excluded replica."""
    rs = _set(pair, hedge_delay_s=0.1)
    try:
        assert rs._hedge_eligible({}) is True
        rs.set_draining(rs.addresses[1], True)
        assert rs._hedge_eligible({}) is False
        rs.set_draining(rs.addresses[1], False)
        assert rs._hedge_eligible({}) is True
        # both replicas excluded (primary + failed): no retry-anyone —
        # the hedge is skipped rather than duplicated onto the primary
        assert rs._hedge_pick(list(PROMPT), frozenset({0, 1})) is None
    finally:
        rs.close()
