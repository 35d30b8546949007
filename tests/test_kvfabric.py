"""Fleet KV fabric tests (tpulab.kvfabric): owner-side publish/export
(write-behind honesty, LRU cap), fetcher-side pull eligibility / cost
gate / single-flight / first-token parity, chaos + failure degradation
to local prefill on BOTH sides, the zero-prefill token-parity
acceptance contract at the engine level, and the full two-replica RPC
fleet (slow) including owner death mid-fetch."""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

import jax.numpy as jnp

from helpers_engine import TokenGate, wait_until
from tpulab import chaos
from tpulab.disagg import KVShipper, prompt_digest
from tpulab.disagg.wire import deserialize_snapshot
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.fleet.router import PrefixAffinityRouter, prefix_digest
from tpulab.kvfabric import KVFabric, fabric_export
from tpulab.kvfabric.fabric import LOGITS_EXTRA
from tpulab.models.transformer import init_transformer_params


@pytest.fixture(scope="module")
def lm():
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)


def _batcher(lm, lanes=1, page_size=8, **kw):
    kw.setdefault("kv_offload", 32 << 20)
    kw.setdefault("kv_publish", True)
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=lanes,
                             max_len=64, page_size=page_size,
                             compute_dtype=jnp.float32, **kw)


def _sampling():
    """Device sampling: varied tokens (greedy on the tiny fixture model
    degenerates into repeats, which would vacuously pass parity)."""
    return SamplingParams(temperature=0.8, device=True, seed=1234)


def _wait_published(cb, digest):
    """Publish is write-behind: wait for the snapshot to land resident
    in the owner's host tier (the fablog row lands synchronously)."""
    wait_until(lambda: ("fab", digest) in cb.kv_offload.store,
               "the fabric publish settled", timeout_s=30)


class _DirectClient:
    """``fetch_kv`` straight into an owner engine's export — the fabric
    exercised without a gRPC hop (the slow RPC test covers the wire)."""

    def __init__(self, owner_cb, mutate=None):
        self.owner = owner_cb
        self.mutate = mutate
        self.calls = 0

    def fetch_kv(self, model_name, digest):
        self.calls += 1
        blob = fabric_export(self.owner, digest)
        return self.mutate(blob) if self.mutate is not None else blob


def _fabric(prompt, client, router=None, **kw):
    """A two-member fabric whose home for ``prompt`` is the OTHER
    member (so a pull is eligible) and whose connect hands back
    ``client`` — by construction only the home is ever dialed."""
    router = router or PrefixAffinityRouter(affinity_tokens=8)
    members = ["replica-a", "replica-b"]
    rd = prefix_digest(prompt, router.affinity_tokens)
    home = router.ranked(rd, members)[0]
    self_key = members[1] if home == members[0] else members[0]
    return KVFabric(self_key, members, lambda k: client, router, **kw)


@pytest.fixture(scope="module")
def owner(lm):
    """One publishing owner engine with a settled snapshot: ``(cb,
    prompt, digest)``.  Read-only for the tests that share it."""
    cb = _batcher(lm)
    prompt = np.random.default_rng(11).integers(0, 64, (13,), np.int32)
    cb.submit(prompt, 2).result(timeout=120)
    digest = prompt_digest(prompt)
    _wait_published(cb, digest)
    yield cb, prompt, digest
    cb.shutdown()


# -- owner side: publish + export ---------------------------------------------

def test_publish_export_wire_roundtrip(owner):
    """A finished prefill publishes once; export wire-encodes it WITHOUT
    consuming the owner's copy (peek, not pop), carries the prefill
    logits row, and repeats."""
    cb, prompt, digest = owner
    assert cb.kv_publishes == 1
    assert ("fablog", digest) in cb.kv_offload.store
    blob = fabric_export(cb, digest)
    assert blob is not None
    arr, header = deserialize_snapshot(blob)
    assert header["digest"] == digest
    assert header["length"] == len(prompt)
    assert header["page_size"] == cb.page_size
    assert LOGITS_EXTRA in header              # first-token parity input
    assert arr.shape[0] == -(-len(prompt) // cb.page_size)
    # the export did NOT evict/consume: both rows still resident
    assert ("fab", digest) in cb.kv_offload.store
    assert ("fablog", digest) in cb.kv_offload.store
    assert fabric_export(cb, digest) is not None   # repeatable
    assert fabric_export(cb, b"\x00" * 16) is None  # unknown digest: miss
    # a re-submit of the same prompt does not re-publish (digest dedup)
    cb.submit(prompt, 2).result(timeout=120)
    assert cb.kv_publishes == 1


def test_export_unarmed_or_untiered_engine_is_a_miss():
    assert fabric_export(SimpleNamespace(kv_offload=None), b"x" * 16) is None
    assert fabric_export(
        SimpleNamespace(kv_offload=object(), kv_publish=False),
        b"x" * 16) is None


def test_export_write_behind_in_flight_is_honest_not_found(owner):
    """Bounded staleness: a registered digest whose snapshot has not
    landed in the host tier yet answers None (the fetcher prefills
    locally) — never a wait, never a partial payload."""
    cb, _, _ = owner
    ghost = b"\x7f" * 16
    with cb._fab_lock:
        cb._fab_handles[ghost] = SimpleNamespace(key=("fab", ghost),
                                                 length=8)
    try:
        assert fabric_export(cb, ghost) is None
    finally:
        with cb._fab_lock:
            cb._fab_handles.pop(ghost, None)


def test_publish_cap_evicts_oldest_with_its_store_rows(lm):
    """The publish registry is a small LRU, not a second cache tier:
    beyond the cap the oldest digest is forgotten AND its host-tier
    rows are removed."""
    cb = _batcher(lm)
    cb.FAB_PUBLISH_CAP = 2
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, (9 + 2 * i,), np.int32)
                   for i in range(3)]
        digs = [prompt_digest(p) for p in prompts]
        for p, d in zip(prompts, digs):
            cb.submit(p, 2).result(timeout=120)
            _wait_published(cb, d)
        assert cb.kv_publishes == 3
        assert cb.fab_handle(digs[0]) is None          # evicted
        assert ("fab", digs[0]) not in cb.kv_offload.store
        assert ("fablog", digs[0]) not in cb.kv_offload.store
        for d in digs[1:]:
            assert fabric_export(cb, d) is not None
    finally:
        cb.shutdown()


# -- fetcher side: eligibility + cost gate ------------------------------------

def _stub_engine(page_size=8, prefix=None, ewma=0.0):
    return SimpleNamespace(
        kv_offload=SimpleNamespace(page_nbytes=1 << 20),
        prefix_cache=prefix, page_size=page_size,
        prefill_ewma_tok_s=ewma)


def test_would_pull_eligibility_gates():
    prompt = np.arange(12, dtype=np.int32)
    fab = _fabric(prompt, client=None)
    eng = _stub_engine()
    assert fab.would_pull(prompt, None, eng) is not None   # eligible
    assert fab.would_pull(prompt, None, None) is None      # no engine
    assert fab.would_pull(
        prompt, None, SimpleNamespace(kv_offload=None)) is None
    assert fab.would_pull(np.arange(1, dtype=np.int32), None, eng) is None
    # host-sampled streams don't survive the hop; device-sampled do
    host = SamplingParams(temperature=0.8, device=False, seed=1)
    assert fab.would_pull(prompt, host, eng) is None
    assert fab.would_pull(prompt, _sampling(), eng) is not None
    assert fab.would_pull(prompt, None, eng, logprobs=True) is None
    # a locally covered prefix never pulls (prefill is ~a tail extend)
    covered = _stub_engine(prefix=SimpleNamespace(
        coverage=lambda p, ps: 99))
    assert fab.would_pull(prompt, None, covered) is None
    # singleton fleet / self-is-home: local state is authoritative
    fab1 = KVFabric("only", ["only"], lambda k: None,
                    PrefixAffinityRouter(affinity_tokens=8))
    assert fab1.would_pull(prompt, None, eng) is None
    home_key = fab.home_of(prompt)
    fab2 = KVFabric(home_key, ["replica-a", "replica-b"],
                    lambda k: None, fab.router)
    assert fab2.would_pull(prompt, None, eng) is None


def test_cost_gate_skips_when_wire_is_slower_than_recompute():
    prompt = np.arange(16, dtype=np.int32)
    fab = _fabric(prompt, client=None)
    # unknown EWMAs: optimistic (the first pulls are the measurement)
    assert not fab._gate_skips(16, _stub_engine(ewma=0.0))
    fab.fetch_bytes_per_s = 1.0                     # 1 B/s: glacial wire
    assert not fab._gate_skips(16, _stub_engine(ewma=0.0))
    eng = _stub_engine(ewma=1e9)                    # prefill ~free
    assert fab._gate_skips(16, eng)
    fab.fetch_bytes_per_s = 1e15                    # wire ~free
    assert not fab._gate_skips(16, eng)
    # the pull path counts the skip and never dials out
    fab.fetch_bytes_per_s = 1.0
    assert fab.pull(prompt, None, eng, shipper=None) is None
    assert fab.snapshot()["cost_gate_skips"] == 1
    assert fab.snapshot()["degrades"] == 0
    fab2 = _fabric(prompt, client=None, cost_gate=False)
    fab2.fetch_bytes_per_s = 1.0
    assert not fab2._gate_skips(16, eng)            # gate disarmable


# -- fetcher side: pull, degradation, single-flight ---------------------------

def test_pull_adopts_and_note_degrade_refunds(lm, owner):
    """A successful pull adopts a host-tier copy; a later admission
    rejection hands its tokens back off the saved ledger."""
    cb_owner, prompt, _ = owner
    cbf = _batcher(lm)
    try:
        client = _DirectClient(cb_owner)
        fab = _fabric(prompt, client)
        shipper = KVShipper(cbf.kv_offload)
        pulled = fab.pull(prompt, None, cbf, shipper)
        assert pulled is not None and client.calls == 1
        assert pulled.length == len(prompt)
        assert not pulled.coalesced
        snap = fab.snapshot()
        assert snap["pulls"] == 1 and snap["degrades"] == 0
        assert snap["recompute_tokens_saved"] == len(prompt)
        assert snap["pull_bytes"] > 0
        assert fab.fetch_bytes_per_s > 0           # cost gate learned
        shipper.manager.discard(pulled.handle)
        fab.note_degrade(pulled)                   # admit rejected after all
        snap = fab.snapshot()
        assert snap["degrades"] == 1
        assert snap["recompute_tokens_saved"] == 0
    finally:
        cbf.shutdown()


def test_pull_degrades_on_miss_corruption_and_geometry(lm, owner):
    cb_owner, prompt, _ = owner
    cbf = _batcher(lm)
    cbf16 = _batcher(lm, page_size=16)             # mismatched geometry
    try:
        shipper = KVShipper(cbf.kv_offload)
        # honest NOT_FOUND (owner has nothing): degrade, no exception
        miss = _fabric(prompt, _DirectClient(cb_owner,
                                             mutate=lambda b: None))
        assert miss.pull(prompt, None, cbf, shipper) is None
        assert miss.snapshot()["degrades"] == 1

        def flip(blob):
            bad = bytearray(blob)
            bad[-1] ^= 0xFF
            return bytes(bad)
        corrupt = _fabric(prompt, _DirectClient(cb_owner, mutate=flip))
        assert corrupt.pull(prompt, None, cbf, shipper) is None
        assert corrupt.snapshot()["degrades"] == 1

        geo = _fabric(prompt, _DirectClient(cb_owner))
        assert geo.pull(prompt, None, cbf16,
                        KVShipper(cbf16.kv_offload)) is None
        assert geo.snapshot()["degrades"] == 1
        assert cbf.dispatch_kinds["mixed"] == 0         # nothing leaked a lane
    finally:
        cbf.shutdown()
        cbf16.shutdown()


@pytest.mark.chaos
@pytest.mark.parametrize("spec", ["fabric.pull=error+1",
                                  "fabric.pull=drop+1"])
def test_chaos_trips_degrade_both_sides(owner, spec):
    """`fabric.pull` fires on the owner's export (honest miss) and the
    fetcher's pull (abandon): either side degrades to a local prefill,
    never a corrupt adoption (docs/ROBUSTNESS.md)."""
    cb_owner, prompt, digest = owner
    with chaos.inject(spec) as sched:              # owner side
        assert fabric_export(cb_owner, digest) is None
        assert sched.fired("fabric.pull") == 1
    assert fabric_export(cb_owner, digest) is not None  # chaos disarmed
    client = _DirectClient(cb_owner)
    fab = _fabric(prompt, client)
    eng = _stub_engine()
    with chaos.inject(spec) as sched:              # fetcher side
        assert fab.pull(prompt, None, eng, shipper=None) is None
        assert sched.fired("fabric.pull") == 1
    assert client.calls == 0                       # tripped before the dial
    assert fab.snapshot()["degrades"] == 1


def test_single_flight_one_fetch_for_concurrent_misses(lm, owner):
    """N concurrent same-digest misses issue exactly ONE FetchKV; every
    waiter shares the leader's snapshot and adopts its OWN copy."""
    cb_owner, prompt, _ = owner
    cbf = _batcher(lm)
    try:
        release, entered = threading.Event(), threading.Event()
        inner = _DirectClient(cb_owner)

        class Blocking:
            calls = 0

            def fetch_kv(self, model_name, digest):
                Blocking.calls += 1
                entered.set()
                assert release.wait(30)
                return inner.fetch_kv(model_name, digest)
        fab = _fabric(prompt, Blocking())
        shipper = KVShipper(cbf.kv_offload)
        results = [None] * 4

        def run(i):
            results[i] = fab.pull(prompt, None, cbf, shipper)
        ts = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        ts[0].start()
        assert entered.wait(30)                    # a leader is in flight
        for t in ts[1:]:
            t.start()
        wait_until(lambda: fab.snapshot()["coalesced"] >= 3,
                   "the three waiters queued", timeout_s=30)
        release.set()
        for t in ts:
            t.join(timeout=60)
        assert Blocking.calls == 1                 # the headline
        assert all(r is not None for r in results)
        handles = {id(r.handle) for r in results}
        assert len(handles) == 4                   # own copy each
        snap = fab.snapshot()
        assert snap["pulls"] == 4 and snap["coalesced"] == 3
        assert sum(r.coalesced for r in results) == 3
        for r in results:
            shipper.manager.discard(r.handle)
    finally:
        cbf.shutdown()


def test_first_token_greedy_header_and_missing_logits_reject(owner):
    cb_owner, prompt, digest = owner
    _, header = deserialize_snapshot(fabric_export(cb_owner, digest))
    fab = _fabric(prompt, client=None)
    assert fab._first_token(header, None) == header["first_token"]
    stripped = {k: v for k, v in header.items() if k != LOGITS_EXTRA}
    from tpulab.disagg import WireFormatError
    with pytest.raises(WireFormatError, match="logits"):
        fab._first_token(stripped, _sampling())


# -- the acceptance contract: zero prefill dispatches + token parity ----------

def test_pull_zero_prefill_dispatches_token_parity(lm):
    """A routed-astray request that pulls decodes with ZERO local
    prefill dispatches and a token stream bit-identical to the local
    prefill it skipped — greedy AND device-sampled (the fetcher replays
    its own sampling on the shipped logits row)."""
    rng = np.random.default_rng(21)
    p_greedy = rng.integers(0, 64, (13,), np.int32)
    p_samp = rng.integers(0, 64, (11,), np.int32)
    cb_owner = _batcher(lm, lanes=2)
    cbf = _batcher(lm, lanes=2)
    try:
        # the owner's own submits are both the parity reference and the
        # publish trigger (identical weights fleet-wide by construction)
        want_g = cb_owner.submit(p_greedy, 8).result(timeout=120)
        want_s = cb_owner.submit(p_samp, 8, sampling=_sampling()).result(
            timeout=120)
        for p in (p_greedy, p_samp):
            _wait_published(cb_owner, prompt_digest(p))
        client = _DirectClient(cb_owner)
        shipper = KVShipper(cbf.kv_offload)
        for p, want, sp in ((p_greedy, want_g, None),
                            (p_samp, want_s, _sampling())):
            fab = _fabric(p, client)
            pulled = fab.pull(p, sp, cbf, shipper)
            assert pulled is not None
            got = list(cbf.submit_shipped(
                p, 8, pulled.first_token, pulled.handle,
                sampling=sp).result(timeout=120))
            assert got == want                     # bit-exact, index 0 on
            assert got[0] == pulled.first_token
        assert cbf.dispatch_kinds["mixed"] == 0         # the headline
        assert cb_owner.dispatch_kinds["mixed"] == 2
    finally:
        cb_owner.shutdown()
        cbf.shutdown()


# -- the publish rides the round in which a first prompt ends -----------------

def test_a_prompt_that_ends_in_a_round_beside_a_decoding_lane_publishes(lm):
    """The publisher's prompt comes in beside a running stream, a chunk a
    round (21 tokens at ``prefill_chunk=8``: three rounds, each with the
    other lane's decode row); the round in which it ends publishes its
    pages and its last row's logits.  A second engine fetches the digest
    and continues with the tokens the publisher itself goes on to
    generate, device-sampled, with no round of its own."""
    rng = np.random.default_rng(41)
    pa = rng.integers(0, 64, (5,), np.int32)
    pb = rng.integers(0, 64, (21,), np.int32)
    cb_owner = _batcher(lm, lanes=2, prefill_chunk=8)
    cbf = _batcher(lm)
    try:
        held = TokenGate(3)
        fa = cb_owner.submit(pa, 30, on_token=held)
        assert held.wait(timeout=60)
        fb = cb_owner.submit(pb, 8, sampling=_sampling())
        held.release()
        want = list(fb.result(timeout=120))
        assert len(fa.result(timeout=120)) == 30
        d = cb_owner.debug_state()["dispatch"]
        # B's three rounds carried A's decode row, and each round that
        # ended a first prompt (A's, B's) was fetched before its successor
        assert d["kinds"]["mixed"] == 4 and d["mixed_decode_rows"] == 3
        assert d["chain"]["breaks"]["host"] == 2
        assert cb_owner.kv_publishes == 2
        _wait_published(cb_owner, prompt_digest(pb))
        pulled = _fabric(pb, _DirectClient(cb_owner)).pull(
            pb, _sampling(), cbf, KVShipper(cbf.kv_offload))
        assert pulled is not None and pulled.first_token == want[0]
        got = list(cbf.submit_shipped(
            pb, 8, pulled.first_token, pulled.handle,
            sampling=_sampling()).result(timeout=120))
        assert got == want
        assert cbf.dispatch_kinds["mixed"] == 0 and cbf.kv_publishes == 0
    finally:
        cb_owner.shutdown()
        cbf.shutdown()


def test_the_snapshots_tail_page_holds_no_row_past_the_prompt(lm):
    """The snapshot is a gather on the pool as the prompt's last round left
    it: with ``kv_publish`` on nothing is enqueued behind that round
    (``_chain_block``: cause ``host``), so the gather is ordered before
    the first decode write.  A prompt of 13 tokens on pages of 8 in a new
    pool (zeros): the tail page's rows 0-4 are the prompt's, rows 5-7
    stay zero in the snapshot though the request went on to write them."""
    prompt = np.random.default_rng(43).integers(0, 64, (13,), np.int32)
    digest = prompt_digest(prompt)
    cb = _batcher(lm)
    try:
        held = TokenGate(5)
        fut = cb.submit(prompt, 12, on_token=held)
        assert held.wait(timeout=60)
        # a block of 8 decode steps is committed: rows 13 .. 20 are written
        pages = list(cb._requests[fut].pages)
        tail_now = np.asarray(cb.pool.kv)[:, pages[1]]
        held.release()
        assert len(fut.result(timeout=120)) == 12
        _wait_published(cb, digest)
        snap = cb.kv_offload.store.peek(("fab", digest))
        breaks = cb.debug_state()["dispatch"]["chain"]["breaks"]
    finally:
        cb.shutdown()
    assert snap.shape[:2] == (2, 2)            # layers, the prompt's pages
    tail = snap[:, 1]                          # (layers, k / v, rows, width)
    assert np.abs(tail[:, :, :5]).min(axis=-1).min() > 0
    assert not tail[:, :, 5:].any()
    assert np.abs(tail_now[:, :, 5:]).min(axis=-1).min() > 0
    np.testing.assert_array_equal(tail_now[:, :, :5],
                                  tail[:, :, :5].reshape(2, 2, 5, -1))
    assert breaks["host"] == 1


def test_a_resumed_request_does_not_publish(lm):
    """A request whose first token was picked elsewhere (a shipment that
    was lost: ``submit_shipped`` with no handle) computes its prompt here
    in a round whose pick is discarded: it is a resume, and a resume
    publishes nothing.  The same prompt submitted as a first prefill
    does."""
    prompt = np.random.default_rng(47).integers(0, 64, (13,), np.int32)
    digest = prompt_digest(prompt)
    ref, cb = _batcher(lm, kv_publish=False), _batcher(lm)
    try:
        want = list(ref.submit(prompt, 6).result(timeout=120))
        got = list(cb.submit_shipped(prompt, 6, want[0], None).result(
            timeout=120))
        assert got == want
        assert cb.dispatch_kinds["mixed"] == 1 and cb.kv_publishes == 0
        assert cb.fab_handle(digest) is None
        assert cb.chain_breaks["host"] == 0
        assert list(cb.submit(prompt, 6).result(timeout=120)) == want
        assert cb.kv_publishes == 1 and cb.fab_handle(digest) is not None
    finally:
        ref.shutdown()
        cb.shutdown()


def test_the_cost_gate_reads_a_prefill_rate_after_one_prompt(lm):
    """``prefill_ewma_tok_s`` is updated where a first prompt's TTFT is
    stamped, in the round: after one prompt the fabric's cost gate has a
    recompute estimate to weigh a fetch against (it read 0.0 for ever on
    the plan every cell runs), and a resume (no TTFT) leaves it alone."""
    prompt = np.random.default_rng(49).integers(0, 64, (13,), np.int32)
    cb = _batcher(lm, kv_publish=False)
    try:
        assert cb.prefill_ewma_tok_s == 0.0
        first = cb.submit(prompt, 2).result(timeout=120)[0]
        rate = cb.prefill_ewma_tok_s
        assert rate > 0.0
        cb.submit_shipped(prompt, 3, first, None).result(timeout=120)
        assert cb.prefill_ewma_tok_s == rate
        fab = _fabric(prompt, client=None)
        fab.fetch_bytes_per_s = 1.0                 # 1 B/s: glacial wire
        assert fab._gate_skips(len(prompt), cb)
        fab.fetch_bytes_per_s = 1e15                # wire ~free
        assert not fab._gate_skips(len(prompt), cb)
    finally:
        cb.shutdown()


# -- metrics ------------------------------------------------------------------

def test_kvfabric_metrics_poll_and_event_hook():
    M = pytest.importorskip("tpulab.utils.metrics")
    if not M.HAVE_PROMETHEUS:
        pytest.skip("prometheus_client unavailable")
    m = M.KVFabricMetrics()
    fab = SimpleNamespace(pulls=3, pull_bytes=4096, coalesced=2,
                          cost_gate_skips=1, degrades=5,
                          recompute_tokens_saved=640)
    m.poll(fab)
    m.poll(fab)                                    # idempotent deltas
    m.observe_pull(0.25, 4096)
    val = m.registry.get_sample_value
    assert val("tpulab_kvfabric_pulls_total") == 3
    assert val("tpulab_kvfabric_pull_bytes_total") == 4096
    assert val("tpulab_kvfabric_coalesced_total") == 2
    assert val("tpulab_kvfabric_cost_gate_skips_total") == 1
    assert val("tpulab_kvfabric_degrades_total") == 5
    assert val("tpulab_kvfabric_recompute_tokens_saved_total") == 640
    assert val("tpulab_kvfabric_pull_seconds_count") == 1
    fab.pulls = 5
    m.poll(fab)
    assert val("tpulab_kvfabric_pulls_total") == 5


# -- the full wire: two served replicas ---------------------------------------

@pytest.mark.slow
def test_rpc_fleet_pull_end_to_end_and_owner_death(lm):
    """Two gRPC replicas with symmetric fabrics: a request routed
    astray pulls over FetchKV (zero prefill dispatches on the serving
    replica, bit-exact stream), and with the owner KILLED mid-fleet the
    same pull degrades to a local prefill without losing the stream."""
    import tpulab
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)
    router_a = PrefixAffinityRouter(affinity_tokens=8)
    router_b = PrefixAffinityRouter(affinity_tokens=8)
    members = []                                   # filled after binding

    def boot(router):
        cb = _batcher(lm, lanes=2)
        fab = KVFabric("pending", lambda: list(members),
                       lambda addr: RemoteInferenceManager(addr),
                       router)
        mgr = tpulab.InferenceManager(max_exec_concurrency=1)
        mgr.serve(port=0, generation_engines={"lm": cb}, kvfabric=fab)
        addr = f"127.0.0.1:{mgr.server.bound_port}"
        fab.self_key = addr
        return mgr, cb, fab, addr
    mgr_a, cb_a, fab_a, addr_a = boot(router_a)
    mgr_b, cb_b, fab_b, addr_b = boot(router_b)
    members.extend([addr_a, addr_b])
    by_addr = {addr_a: (mgr_a, cb_a, fab_a), addr_b: (mgr_b, cb_b, fab_b)}
    clients = {a: RemoteInferenceManager(a) for a in members}
    killed = False
    try:
        prompt = np.random.default_rng(31).integers(0, 64, (14,), np.int32)
        rd = prefix_digest(prompt, 8)
        home = router_a.ranked(rd, members)[0]
        astray = members[1] if home == members[0] else members[0]
        _, cb_home, _ = by_addr[home]
        _, cb_astray, fab_astray = by_addr[astray]
        # 1. warm the home replica (publishes); its stream is the reference
        want = list(GenerateStreamClient(clients[home], "lm").generate(
            prompt, 8, temperature=0.8, device_sampling=True, seed=1234))
        _wait_published(cb_home, prompt_digest(prompt))
        # 2. the astray request pulls instead of prefilling
        got = list(GenerateStreamClient(clients[astray], "lm").generate(
            prompt, 8, temperature=0.8, device_sampling=True, seed=1234))
        assert got == want
        assert cb_astray.dispatch_kinds["mixed"] == 0   # the acceptance bar
        snap = fab_astray.snapshot()
        assert snap["pulls"] == 1 and snap["degrades"] == 0
        assert snap["recompute_tokens_saved"] == len(prompt)
        # 3. owner death mid-fleet: a second digest homed on the same
        # replica now degrades to a local prefill — stream intact
        rng = np.random.default_rng(32)
        while True:
            p2 = rng.integers(0, 64, (12,), np.int32)
            if router_a.ranked(prefix_digest(p2, 8), members)[0] == home:
                break
        dead_mgr, dead_cb, _ = by_addr[home]
        dead_mgr.shutdown()
        dead_cb.shutdown()
        killed = True
        got2 = list(GenerateStreamClient(clients[astray], "lm").generate(
            p2, 6, temperature=0.8, device_sampling=True, seed=77))
        assert len(got2) == 6                      # served, not stranded
        assert cb_astray.dispatch_kinds["mixed"] == 1   # the local fallback ran
        assert fab_astray.snapshot()["degrades"] == 1
    finally:
        for c in clients.values():
            c.close()
        for addr, (m, cb, fab) in by_addr.items():
            fab.close()
            if not (killed and addr == home):
                m.shutdown()
                cb.shutdown()
