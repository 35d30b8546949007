"""Speculative decoding inside the fused paged decode blocks.

Parity discipline (the decode-block contract extended): speculation is a
DISPATCH-SHAPE change, never a content change — the speculative greedy
stream must be bit-identical to non-speculative greedy (and the seeded
device-sampled stream to its plain reference), including mid-block EOS,
steps-limit truncation, and page-boundary crossings.  The host-sync
guard pins the feature's point: at acceptance > 0 one blocking fetch
covers MORE than K tokens, so syncs per emitted token strictly decrease
vs plain K-blocks.  The fallback guards pin the degradation story: an
adversarial draft converges to plain-block behavior, host-sampled lanes
never speculate, a chaos-tripped verify degrades the lane without a
corrupt or duplicated emission, and draft-table pages always come home.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from helpers_engine import FirstTokenGate
from tpulab import chaos
from tpulab.engine.paged import (ContinuousBatcher, SamplingParams,
                                 _PagedRequest)
from tpulab.models.transformer import (early_exit_draft,
                                       init_transformer_params,
                                       make_generate_fn)


@pytest.fixture(scope="module")
def lm():
    p = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64)
    # trained-model emulation: shrink
    # the post-exit layer's output projections so the 1-layer early-exit
    # draft actually agrees with the target — raw random tails pin
    # acceptance to ~0 and the early-exit tests would measure nothing
    for w in ("wo", "w2"):
        p["layer1"][w] = p["layer1"][w] * 0.05
    return p


@pytest.fixture(scope="module")
def dense(lm):
    return make_generate_fn(lm, n_heads=2, n_layers=2, max_len=96,
                            compute_dtype=jnp.float32)


def _batcher(lm, draft="early_exit", k=8, **kw):
    """draft: None = plain; "early_exit" = 1-layer early-exit draft;
    "self" = the target itself (perfect draft, acceptance 1); or an
    explicit param tree (draft_n_layers then required in kw)."""
    kw.setdefault("lanes", 2)
    kw.setdefault("max_len", 96)
    # two tables per lane want roughly double the plain pool
    kw.setdefault("n_pages", 2 * kw["lanes"] * ((kw["max_len"] + 7) // 8)
                  + 1)
    if draft == "early_exit":
        draft, kw["draft_n_layers"] = early_exit_draft(lm, 1), 1
    elif draft == "self":
        draft, kw["draft_n_layers"] = lm, 2
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, page_size=8,
                             compute_dtype=jnp.float32, decode_block=k,
                             draft_params=draft, **kw)


def test_spec_greedy_parity_with_page_crossings(lm, dense):
    """Speculative greedy == dense greedy == plain-block greedy for
    prompts that put the write position mid-page at block start and for
    decode runs that cross page boundaries inside a block — and the
    speculative path actually ran (not a silent fallback)."""
    cb = _batcher(lm)
    try:
        rng = np.random.default_rng(5)
        cases = [(rng.integers(0, 64, (n,), np.int32), s)
                 for n, s in ((5, 20), (8, 17), (13, 30), (1, 9))]
        for p, s in cases:
            got = list(cb.submit(p, s).result(timeout=120))
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(dense(p[None, :], s)[0]))
        assert cb.spec_dispatches > 0
        assert cb.spec_tokens_accepted > 0
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_steps_limit_mid_block(lm, dense):
    """steps smaller than (and not divisible by) the draft K: the
    device-side steps-remaining mask truncates the emission exactly at
    the budget, and the over-budget verify/draft writes never corrupt a
    later request's pages."""
    p = np.random.default_rng(9).integers(0, 64, (4,), np.int32)
    cb = _batcher(lm, lanes=1)
    try:
        for s in (2, 5, 9):
            got = list(cb.submit(p, s).result(timeout=120))
            assert len(got) == s
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(dense(p[None, :], s)[0]))
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_eos_mid_block(lm, dense):
    """A stop token hit mid-acceptance ends the lane on device: the stop
    token is the final emitted token, later candidates are discarded,
    and the lane's target AND draft pages all come home."""
    p = np.random.default_rng(8).integers(0, 64, (5,), np.int32)
    ref = list(np.asarray(dense(p[None, :], 16)[0]))
    stop = ref[5]
    want = ref[:ref.index(stop) + 1]
    cb = _batcher(lm, lanes=1)
    try:
        got = list(cb.submit(p, 16, stop_tokens=[stop]).result(timeout=120))
        assert got == want
        # stop at the prefill-emitted first token still terminates
        got1 = list(cb.submit(p, 16,
                              stop_tokens=[ref[0]]).result(timeout=120))
        assert got1 == ref[:1]
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_device_sampled_parity(lm):
    """Seeded device-sampled streams are identical with and without
    speculation: the target's per-position choice folds (seed, position)
    only, and the verify forward evaluates exactly the plain stream's
    logits along the accepted path."""
    p = np.random.default_rng(6).integers(0, 64, (5,), np.int32)
    sp = dict(temperature=0.9, seed=1234, device=True)
    cb = _batcher(lm, draft=None, lanes=1)
    try:
        want = list(cb.submit(
            p, 20, sampling=SamplingParams(**sp)).result(timeout=120))
    finally:
        cb.shutdown()
    cb = _batcher(lm, draft="self", lanes=1)
    try:
        got = list(cb.submit(
            p, 20, sampling=SamplingParams(**sp)).result(timeout=120))
        assert got == want and len(got) == 20
        assert cb.spec_dispatches > 0
        # a perfect draft reaches full acceptance under sampling too
        assert cb.spec_acceptance > 0.9
    finally:
        cb.shutdown()


def test_spec_logprobs_parity(lm):
    """logprobs=True through the speculative path: same tokens, same
    on-device f32 log-softmax stream as the plain path (allclose: the
    chunked verify may fuse differently)."""
    p = np.random.default_rng(12).integers(0, 64, (6,), np.int32)
    outs = {}
    for mode in (None, "self"):
        cb = _batcher(lm, draft=mode, lanes=1)
        try:
            outs[mode] = cb.submit(p, 12, logprobs=True).result(timeout=120)
        finally:
            cb.shutdown()
    assert list(outs["self"][0]) == list(outs[None][0])
    np.testing.assert_allclose(outs["self"][1], outs[None][1], rtol=1e-5,
                               atol=1e-6)


def test_spec_host_syncs_strictly_decrease(lm):
    """THE regression guard (the PR 4 host-sync pattern, multiplied):
    at acceptance > 0 a speculative request's blocking decode fetches
    strictly undercut the plain K-block run of the same workload —
    each sync covers up to K+1 accepted tokens instead of K."""
    p = np.random.default_rng(7).integers(0, 64, (5,), np.int32)
    res = {}
    for mode in (None, "self"):
        cb = _batcher(lm, draft=mode, lanes=1)
        try:
            cb.submit(p, 80).result(timeout=300)   # warm compiles
            s0, t0 = cb.decode_host_syncs, cb.tokens_generated
            out = list(cb.submit(p, 80).result(timeout=300))
            res[mode] = (cb.decode_host_syncs - s0,
                         cb.tokens_generated - t0, out)
        finally:
            cb.shutdown()
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    assert res["self"][2] == res[None][2]          # token parity
    assert res["self"][1] == res[None][1] == 80    # accepted-only counting
    syncs_spec, syncs_plain = res["self"][0], res[None][0]
    assert syncs_spec < syncs_plain, (syncs_spec, syncs_plain)
    assert syncs_spec / 80 < syncs_plain / 80      # per emitted token


def test_spec_adaptive_fallback_adversarial_draft(lm, dense):
    """An adversarial draft (independent random weights, ~zero
    acceptance) converges to plain-block decode: the per-lane acceptance
    EWMA falls through the floor within a few dispatches, the lane
    degrades for the rest of the request (draft pages returned), output
    stays exactly greedy, and subsequent dispatches are plain."""
    # the target with a NEGATED lm head: proposes the argmin, so it never
    # agrees with the target's argmax (a random tiny draft is not
    # adversarial — degenerate models collapse to the same fixed token)
    bad = dict(early_exit_draft(lm, 2))
    bad["lm_head"] = -np.asarray(lm["embed"]).T
    p = np.random.default_rng(4).integers(0, 64, (5,), np.int32)
    cb = _batcher(lm, draft=bad, draft_n_layers=2, lanes=1)
    try:
        got = list(cb.submit(p, 40).result(timeout=300))
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense(p[None, :], 40)[0]))
        assert cb.spec_fallbacks >= 1
        assert cb.spec_acceptance < 0.3
        # converged: most dispatches ran plain after the degrade
        assert cb.decode_dispatches > cb.spec_dispatches
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_host_sampled_lane_never_speculates(lm):
    """Host-sampled (top_k) lanes never enter the speculative path: their
    seeded host-PRNG stream requires per-token logits fetches, so the
    whole dispatch stays plain (K=1) and matches the plain reference."""
    ph = np.random.default_rng(2).integers(0, 64, (4,), np.int32)
    cb1 = _batcher(lm, draft=None, k=1, lanes=1)
    try:
        want = list(cb1.submit(ph, 10, sampling=SamplingParams(
            temperature=0.8, top_k=8, seed=55)).result(timeout=120))
    finally:
        cb1.shutdown()
    cb = _batcher(lm, draft="self", lanes=2)
    try:
        got = list(cb.submit(ph, 10, sampling=SamplingParams(
            temperature=0.8, top_k=8, seed=55)).result(timeout=120))
        assert got == want
        assert cb.spec_dispatches == 0
        assert cb.spec_tokens_drafted == 0
    finally:
        cb.shutdown()


@pytest.mark.chaos
@pytest.mark.parametrize("spec", ["engine.verify=error+1",
                                  "engine.verify=drop+1"])
def test_chaos_verify_trip_degrades_lane_to_plain(lm, dense, spec):
    """A tripped verify dispatch (error or drop) degrades the lane to
    plain blocks for the rest of the request: the trip fires BEFORE
    anything is dispatched, so no token is ever duplicated, lost, or
    corrupted — the output is exactly the greedy sequence — and the
    draft table's pages return to the pool."""
    p = np.random.default_rng(31).integers(0, 64, (5,), np.int32)
    cb = _batcher(lm, draft="self", lanes=1)
    try:
        with chaos.inject(spec) as sched:
            got = list(cb.submit(p, 20).result(timeout=300))
            assert sched.fired("engine.verify") == 1
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense(p[None, :], 20)[0]))
        assert cb.spec_fallbacks >= 1
        assert cb.spec_dispatches == 0      # degraded before the first one
        # the NEXT request speculates again (degradation is per-request)
        got2 = list(cb.submit(p, 20).result(timeout=300))
        np.testing.assert_array_equal(
            np.asarray(got2), np.asarray(dense(p[None, :], 20)[0]))
        assert cb.spec_dispatches > 0
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_reserve_shrinks_draft_k_before_target_pages(lm):
    """Draft page-table accounting under pool pressure: when the pool
    cannot cover both tables at full K, the DRAFT shortfall shrinks the
    block k — target reservations are never released to feed the draft
    — and pages past the shrunk horizon (and degraded drafts' pages) go
    straight back to the pool."""
    cb = _batcher(lm, draft="self", lanes=1, max_len=64, n_pages=4)
    try:
        free0 = cb.pool.free_pages            # 3 usable pages
        req = _PagedRequest(np.ones(4, np.int32), 40)
        req.tokens_out = [1]
        req.length = 4
        kd, parts = cb._reserve_spec_pages([(0, req)], 8)
        # want 9 appends -> 2 target pages, but only 1 page left for the
        # draft: cov_d = 4, cap = 4, kd snaps to 2 and the surplus target
        # page is returned
        assert kd == 2, kd
        assert len(parts) == 1
        assert len(req.pages) == 1 and len(req.draft_pages) == 1
        assert cb.pool.free_pages == free0 - 2
        # degrade returns the draft table's pages (rejected-draft pages
        # are never leaked — the PR 5 swap-in-leak regression class)
        cb._degrade_spec(req)
        assert req.draft_pages == [] and req.draft_len == 0
        assert cb.pool.free_pages == free0 - 1
        cb.pool.release_pages(req.pages)
        assert cb.pool.free_pages == free0
        # a pool that cannot cover ONE draft append refuses speculation
        # but keeps the target reservation for the plain fallback
        grab = [cb.pool.allocate_page() for _ in range(free0 - 1)]
        req2 = _PagedRequest(np.ones(4, np.int32), 40)
        req2.tokens_out = [1]
        req2.length = 4
        kd2, parts2 = cb._reserve_spec_pages([(0, req2)], 8)
        assert kd2 == 0 and parts2 == []
        assert len(req2.pages) == 1 and req2.draft_pages == []
        cb.pool.release_pages(req2.pages)
        cb.pool.release_pages(grab)
        assert cb.pool.free_pages == free0
    finally:
        cb.shutdown()


def test_spec_under_pool_pressure_completes(lm, dense):
    """A pool too tight for double tables still completes every request
    exactly (shrunken spec blocks, plain fallbacks, starved-lane skips —
    whatever it takes), and all pages come home."""
    cb = _batcher(lm, lanes=2, max_len=48, n_pages=9)   # 8 usable pages
    try:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 64, (6,), np.int32) for _ in range(4)]
        futs = [cb.submit(p, 16) for p in prompts]
        for p, f in zip(prompts, futs):
            got = list(f.result(timeout=300))
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(dense(p[None, :], 16)[0]))
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_preempt_resume_regenerates_exactly(lm, dense):
    """Preemption with a live draft table: the draft pages are released
    at eviction (never snapshotted) and the resume's warm-up regenerates
    the draft KV exactly — both the victim's and the preemptor's outputs
    equal the dense reference, and the pool balances."""
    p_low = np.random.default_rng(31).integers(0, 64, (6,), np.int32)
    p_hi = np.random.default_rng(32).integers(0, 64, (5,), np.int32)
    cb = _batcher(lm, draft="self", lanes=1, max_len=64, n_pages=17)
    try:
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 24, on_token=started)
        assert started.wait(timeout=120)
        f_hi = cb.submit(p_hi, 4, priority=10)
        started.release()
        got_hi = list(f_hi.result(timeout=300))
        got_low = list(f_low.result(timeout=300))
        assert cb.preemptions >= 1
        assert cb.spec_draft_prefills >= 2   # initial warm-up + re-warm
        np.testing.assert_array_equal(
            np.asarray(got_low), np.asarray(dense(p_low[None, :], 24)[0]))
        np.testing.assert_array_equal(
            np.asarray(got_hi), np.asarray(dense(p_hi[None, :], 4)[0]))
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_spec_streaming_callbacks_in_order(lm):
    """Per-token on_token callbacks survive speculative unpacking: every
    accepted token, in order, with its index, matching the future."""
    cb = _batcher(lm, lanes=1)
    try:
        streamed = []
        p = np.random.default_rng(4).integers(0, 64, (4,), np.int32)
        fut = cb.submit(p, 13,
                        on_token=lambda tok, i: streamed.append((i, tok)))
        final = fut.result(timeout=120)
        assert [i for i, _t in streamed] == list(range(13))
        assert [t for _i, t in streamed] == list(final)
    finally:
        cb.shutdown()


def test_spec_metrics_accepted_only_and_poll(lm):
    """GenerationMetrics: spec_tokens_drafted / spec_tokens_accepted
    counters and the acceptance-rate gauge export, and
    tokens_per_dispatch counts ACCEPTED tokens only — an adversarial
    draft's rejected proposals must not inflate it."""
    pytest.importorskip("prometheus_client")
    from prometheus_client import CollectorRegistry

    from tpulab.utils.metrics import GenerationMetrics

    bad = dict(early_exit_draft(lm, 2))          # argmin draft: rejects
    bad["lm_head"] = -np.asarray(lm["embed"]).T
    cb = _batcher(lm, draft=bad, draft_n_layers=2, lanes=1)
    gm = GenerationMetrics(registry=CollectorRegistry())
    try:
        p = np.random.default_rng(3).integers(0, 64, (5,), np.int32)
        out = list(cb.submit(p, 24).result(timeout=300))
        gm.poll(cb)
        val = gm.registry.get_sample_value
        drafted = val("tpulab_llm_spec_tokens_drafted_total")
        accepted = val("tpulab_llm_spec_tokens_accepted_total")
        assert drafted == cb.spec_tokens_drafted > 0
        assert accepted == cb.spec_tokens_accepted
        assert accepted <= drafted
        assert val("tpulab_llm_spec_acceptance_rate") == pytest.approx(
            cb.spec_acceptance)
        assert val("tpulab_llm_spec_fallbacks_total") == cb.spec_fallbacks
        # tokens_per_dispatch reflects emitted (accepted) tokens only:
        # tokens_generated is exactly the output length, drafted-rejected
        # proposals appear nowhere in it
        assert cb.tokens_generated == len(out)
        assert val("tpulab_llm_tokens_per_dispatch") == pytest.approx(
            cb.tokens_generated / cb.decode_dispatches)
    finally:
        cb.shutdown()


def test_spec_trace_spans_carry_accepted(lm):
    """Decode trace spans from speculative blocks carry ``accepted=``
    next to the existing ``block=`` tag."""
    from tpulab.utils.tracing import ChromeTraceRecorder

    tr = ChromeTraceRecorder()
    cb = _batcher(lm, draft="self", lanes=1, trace=tr)
    try:
        p = np.random.default_rng(5).integers(0, 64, (5,), np.int32)
        cb.submit(p, 12).result(timeout=120)
    finally:
        cb.shutdown()
    spans = [e for e in list(tr._events)
             if e.get("name") == "decode" and "accepted" in e.get("args", {})]
    assert spans, "no decode span carried accepted="
    assert all("block" in s["args"] for s in spans)


def test_spec_admission_cost_factor(lm):
    """Cost-aware admission treats speculative requests as bigger:
    the batcher advertises a 2x cost factor (second page table +
    drafted-but-rejected compute) and the controller's capacity gate
    applies it."""
    from tpulab.serving import AdmissionConfig, AdmissionController

    cb_spec = _batcher(lm, draft="self", lanes=1, max_len=48)
    cb_plain = _batcher(lm, draft=None, lanes=1, max_len=48)
    try:
        assert cb_spec.admission_cost_factor == 2.0
        assert cb_plain.admission_cost_factor == 1.0

        class _Load:
            page_size = 8
            lanes = 4
            active_lanes = 0
            queued_requests = 0

            class pool:
                free_pages = 10

        load = _Load()
        ctrl = AdmissionController(AdmissionConfig(), load=load)
        assert ctrl._capacity_ok_locked(50)       # 50 <= 80 free
        load.admission_cost_factor = 2.0
        assert not ctrl._capacity_ok_locked(50)   # 100 > 80 free
        assert ctrl._capacity_ok_locked(40)       # 80 <= 80
    finally:
        cb_spec.shutdown()
        cb_plain.shutdown()


# -- transient-degrade probes (re-enable speculation within a request) -----
def test_spec_probe_policy_state_machine(lm):
    """The probe state machine in isolation: a probe=True degrade arms a
    countdown, SPEC_PROBE_INTERVAL plain consumes later the lane
    re-enters speculation AS A PROBE with its EWMA reset to the floor;
    a probe=False (chaos) degrade never arms one."""
    cb = _batcher(lm, draft="self", lanes=1)
    try:
        req = _PagedRequest(np.ones(4, np.int32), 40)
        req.tokens_out = [1]
        cb._degrade_spec(req, probe=True)
        assert not req.spec_enabled and req.spec_probe_in == \
            cb.SPEC_PROBE_INTERVAL
        for i in range(cb.SPEC_PROBE_INTERVAL - 1):
            cb._probe_countdown_locked(req)
            assert not req.spec_enabled, i
        cb._probe_countdown_locked(req)
        assert req.spec_enabled and req.spec_probing
        assert req.spec_ewma == cb.spec_accept_floor
        assert req.spec_probe_in is None
        assert cb.spec_probes == 1

        # chaos degrade: permanent — the countdown never arms
        req2 = _PagedRequest(np.ones(4, np.int32), 40)
        req2.tokens_out = [1]
        cb._degrade_spec(req2)          # probe=False
        assert req2.spec_probe_in is None
        for _ in range(3 * cb.SPEC_PROBE_INTERVAL):
            cb._probe_countdown_locked(req2)
        assert not req2.spec_enabled and not req2.spec_probing
    finally:
        cb.shutdown()


def test_spec_probe_recovers_after_transient_degrade(lm, dense):
    """A lane degraded by a TRANSIENT acceptance dip recovers: with a
    perfect (self) draft, a forced EWMA-style degrade runs plain blocks
    for SPEC_PROBE_INTERVAL dispatches, then one probe block whose
    perfect acceptance re-enables speculation for the rest of the
    request — and the emitted stream stays exactly greedy throughout."""
    import time as _t
    p = np.random.default_rng(17).integers(0, 64, (5,), np.int32)
    cb = _batcher(lm, draft="self", lanes=1, max_len=96)
    try:
        started = FirstTokenGate()
        fut = cb.submit(p, 60, on_token=started)
        assert started.wait(timeout=120)
        # transient degrade, exactly what a low-acceptance stretch does
        with cb._cv:
            req = next(r for r in cb._active if r is not None)
            cb._degrade_spec(req, probe=True)
        spec_after_degrade = cb.spec_dispatches
        started.release()
        got = list(fut.result(timeout=300))
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense(p[None, :], 60)[0]))
        assert cb.spec_probes >= 1
        assert cb.spec_probe_recoveries >= 1
        # recovery is real: speculative dispatches resumed after the probe
        assert cb.spec_dispatches > spec_after_degrade
        deadline = _t.monotonic() + 10
        while (_t.monotonic() < deadline
               and cb.pool.free_pages != cb.pool.n_pages - 1):
            _t.sleep(0.01)
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


@pytest.mark.slow  # heavyweight e2e; tier-1 runtime headroom (see ROADMAP)
def test_spec_probe_stays_degraded_on_adversarial_draft(lm, dense):
    """Probes on a lane whose draft is truly bad keep failing closed: the
    argmin draft degrades the lane via the EWMA, periodic probes fire
    (spec_probes advances) but never recover (zero recoveries), output
    stays exactly greedy, and between probes the lane runs plain."""
    bad = dict(early_exit_draft(lm, 2))
    bad["lm_head"] = -np.asarray(lm["embed"]).T
    p = np.random.default_rng(4).integers(0, 64, (5,), np.int32)
    cb = _batcher(lm, draft=bad, draft_n_layers=2, lanes=1, max_len=128)
    try:
        got = list(cb.submit(p, 80).result(timeout=300))
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense(p[None, :], 80)[0]))
        assert cb.spec_fallbacks >= 2      # initial degrade + failed probe
        assert cb.spec_probes >= 1
        assert cb.spec_probe_recoveries == 0
        assert cb.decode_dispatches > cb.spec_dispatches
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1
