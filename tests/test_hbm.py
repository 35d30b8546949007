"""Unified HBM economy tests (tpulab.hbm): byte-accurate ledger invariant
(claims == tracked allocator gauges after every arbiter op), both
pressure directions end-to-end with bit-exact results (a hot-model
acquire demotes live-but-idle KV and the resumed stream matches; a KV
burst evicts a cold model that swaps back bit-exact), leased/pinned and
in-flight protection, the no-livelock guard, chaos degradation to
static-budget behavior, per-jit scratch claims, admission's unified
headroom, and the Status/poll_load gauge."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab import chaos
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.hbm import (KV_TENANT, SCRATCH_TENANT, WEIGHTS_TENANT,
                        DeviceHBMLedger, HBMArbiter)
from tpulab.models.transformer import init_transformer_params
from tpulab.modelstore import WeightMultiplexer

#: one page of the test pool (n_layers=1, page_size=8, n_kv=2, head_dim=16,
#: f32): every sizing below is phrased in pages of this
PN = 1 * 2 * 8 * 2 * 16 * 4


@pytest.fixture(scope="module")
def lm():
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=1, d_ff=64)


def _batcher(lm, arb, lanes=2, max_len=24, n_pages=4, **kw):
    return ContinuousBatcher(lm, n_heads=2, n_layers=1, lanes=lanes,
                             max_len=max_len, page_size=8,
                             n_pages=n_pages, compute_dtype=jnp.float32,
                             kv_offload=True, hbm=arb, **kw)


class _Servable:
    """Byte-sized dense servable (same adapter protocol as
    CompiledModelAdapter/BatcherAdapter)."""

    def __init__(self, words: int, resident: bool = True):
        self._words = words
        self.device_params = (jax.device_put(self.rebuild())
                              if resident else None)

    def rebuild(self):
        return {"w": jnp.arange(self._words, dtype=jnp.float32)}

    def resident(self):
        return self.device_params is not None

    def param_bytes(self):
        return self._words * 4

    def busy(self):
        return False

    def detach(self):
        dev, self.device_params = self.device_params, None
        return dev

    def on_detached(self):
        pass

    def attach(self, host_tree):
        self.device_params = jax.device_put(host_tree)

    def rebuild_tree(self):
        return self.rebuild()


class _Adapter:
    def __init__(self, s):
        self._s = s

    def resident(self):
        return self._s.resident()

    def param_bytes(self):
        return self._s.param_bytes()

    def busy(self):
        return self._s.busy()

    def detach(self):
        return self._s.detach()

    def on_detached(self):
        pass

    def attach(self, t):
        self._s.attach(t)

    def rebuild(self):
        return self._s.rebuild()


# -- ledger -------------------------------------------------------------------

def test_ledger_claims_release_resize_verify():
    led = DeviceHBMLedger(1000)
    led.claim("kv", "pool", 600)
    with pytest.raises(ValueError):
        led.claim("kv", "pool", 1)            # double-claim is the bug
    led.claim("weights", "m1", 300)
    assert led.total_claimed == 900 and led.headroom_bytes == 100
    assert led.tenant_bytes("kv") == 600 and led.tenant_claims("kv") == 1
    led.resize("kv", "pool", 500)             # elastic pool shrank
    assert led.headroom_bytes == 200
    assert led.release("weights", "m1") == 300
    assert led.release("weights", "m1") == 0  # idempotent
    # verify cross-checks claims against live gauges, per tenant
    assert led.verify({"kv": 500}) == {}
    assert led.verify({"kv": 499}) == {"kv": (500, 499)}
    # over-commit reports honestly (negative headroom, never clamped)
    led.claim("scratch", ("jit", 0), 700)
    assert led.headroom_bytes == -200


def test_ledger_invariant_against_tracked_allocators():
    """The acceptance invariant: after EVERY arbiter op, per-tenant
    claims sum exactly to the tracked device-allocator gauge backing
    that tenant (here: two real TpuRawAllocators holding live HBM
    arrays, exercised through claim / request-with-pressure / release /
    deny)."""
    from tpulab.tpu.allocators import make_tpu_allocator
    akv, aw = make_tpu_allocator(), make_tpu_allocator()
    arb = HBMArbiter(64 * 1024, measure_scratch=False)
    state = {}

    def kv_reclaim(nbytes):
        # free half the KV block (demote-analog): deallocate + resize
        addr, size = state["kv"]
        akv.deallocate_node(addr)
        new = size // 2
        addr2, _ = akv.allocate_array((new,), jnp.uint8)
        state["kv"] = (addr2, new)
        arb.mirror_claim("kv", "pool", akv.bytes_in_use)
        return size - new

    arb.register("kv", reclaim=kv_reclaim,
                 gauge=lambda: akv.bytes_in_use)
    arb.register("weights", gauge=lambda: aw.bytes_in_use)

    def check():
        assert arb.verify() == {}
        assert (arb.ledger.total_claimed
                == akv.bytes_in_use + aw.bytes_in_use)

    addr, _ = akv.allocate_array((48 * 1024,), jnp.uint8)
    state["kv"] = (addr, 48 * 1024)
    arb.claim("kv", "pool", akv.bytes_in_use)
    check()
    # request with headroom: grant, then back the claim with real bytes
    assert arb.request("weights", "m1", 8 * 1024, timeout=1.0)
    aw.allocate_array((8 * 1024,), jnp.uint8)
    check()
    # request beyond headroom: pressure presses the kv tenant, which
    # frees real bytes and resizes its claim — grant lands byte-exact
    assert arb.request("weights", "m2", 16 * 1024, timeout=5.0)
    aw.allocate_array((16 * 1024,), jnp.uint8)
    check()
    assert arb.demotions_forced >= 1
    # an unfillable request: pressure may still reclaim (and the ledger
    # follows every real free), but the request DENIES and nothing is
    # ever claimed for the denied requester
    assert not arb.request("weights", "m3", 64 * 1024, timeout=0.5)
    assert arb.denials == 1
    assert arb.ledger.tenant_claims("weights") == 2  # m1+m2 only, no m3
    check()
    # release mirrors a real free
    for a_addr in list(aw._buffers):
        aw.deallocate_node(a_addr)
    arb.release("weights", "m1")
    arb.release("weights", "m2")
    check()


# -- pressure directions end-to-end ------------------------------------------

def test_model_acquire_demotes_live_idle_kv_stream_resumes_exact(lm):
    """Direction 1 (the acceptance flow): a hot-model acquire presses the
    KV tenant — the live-but-idle stream's KV demotes to the host tier,
    the pool shrinks, the model swaps in from the host tier; after the
    lease releases, the pool regrows (evicting the model: direction 2 in
    the same life) and the resumed stream's tokens are bit-exact."""
    prompt = np.arange(4, 12, dtype=np.int32)
    steps = 48                                # outgrows the 5-page base
    # reference stream: a plain batcher, no arbiter, roomy fixed pool
    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=1, lanes=1,
                               max_len=56, page_size=8, n_pages=12,
                               compute_dtype=jnp.float32)
    try:
        ref = [int(t) for t in
               ref_cb.submit(prompt, steps).result(timeout=120)]
    finally:
        ref_cb.shutdown()

    b = _Servable(words=12 * PN // 4, resident=False)  # 12 pages of HBM
    arb = HBMArbiter(13 * PN, measure_scratch=False)
    # decode_block=1: one dispatch per token, so the acquire's squeeze
    # catches the stream mid-decode (live-but-idle between ticks)
    cb = _batcher(lm, arb, lanes=1, max_len=56, n_pages=5,
                  decode_block=1)
    mux = WeightMultiplexer(b.param_bytes(), hbm=arb)
    mux.register("b", _Adapter(b), params=b.rebuild())
    assert mux.state_of("b") == "cold"
    try:
        decoding = threading.Event()
        toks = []

        def on_tok(t, i):
            toks.append(t)
            if i >= 3:
                decoding.set()
                time.sleep(0.01)  # throttle the stream so the acquire's
                #                   squeeze catches it mid-decode

        fut = cb.submit(prompt, steps, on_token=on_tok)
        assert decoding.wait(60)              # live, mid-decode
        deadline = time.monotonic() + 30
        while cb.pool.n_pages <= 5 and time.monotonic() < deadline:
            time.sleep(0.01)                  # the probe grows the pool
        grown = cb.pool.n_pages
        assert grown > 5                      # the stream won pool bytes
        lease = mux.acquire("b", timeout=60)  # squeezes the KV tenant
        assert mux.state_of("b") == "hot"
        assert cb.pool.n_pages < grown        # pool gave the bytes back
        assert cb.hbm_demotions >= 1          # the live lane was demoted
        assert cb.kv_offload.swap_outs + cb.kv_offload.swap_failures >= 1
        assert arb.verify() == {}             # ledger == gauges mid-squeeze
        lease.release()
        got = [int(t) for t in fut.result(timeout=120)]
        assert got == ref                     # resumed stream bit-exact
        assert got == toks
        assert mux.evictions >= 1             # regrow pressed the model out
        assert arb.verify() == {}
    finally:
        cb.shutdown()
        mux.close()


def test_kv_burst_evicts_cold_model_swaps_back_bit_exact(lm):
    """Direction 2 (the acceptance flow): a KV burst grows the pool by
    evicting the cold unleased model to the host tier; the model's next
    acquire squeezes back in and serves bit-identical weights (host-tier
    promotion, not a rebuild)."""
    b = _Servable(words=4 * PN // 4)          # 4 pages of HBM, hot
    fwd = jax.jit(lambda p: (p["w"] * 3.0).sum())
    arb = HBMArbiter(8 * PN + PN // 2, measure_scratch=False)
    cb = _batcher(lm, arb, lanes=2, max_len=24, n_pages=4)
    mux = WeightMultiplexer(b.param_bytes(), hbm=arb)
    mux.register("b", _Adapter(b))
    assert mux.state_of("b") == "hot"
    ref_out = float(np.asarray(fwd(b.device_params)))
    # reference burst: plain batcher with the full-size fixed pool
    prompts = [np.arange(8, dtype=np.int32) % 64,
               (np.arange(8, dtype=np.int32) * 5) % 64]
    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=1, lanes=2,
                               max_len=24, page_size=8, n_pages=8,
                               compute_dtype=jnp.float32)
    try:
        ref = [[int(t) for t in ref_cb.submit(p, 16).result(timeout=120)]
               for p in prompts]
    finally:
        ref_cb.shutdown()
    try:
        futs = [cb.submit(p, 16) for p in prompts]
        got = [[int(t) for t in f.result(timeout=120)] for f in futs]
        assert got == ref                     # burst tokens bit-exact
        assert mux.drain()
        assert mux.evictions >= 1             # the burst pressed B out
        assert mux.state_of("b") == "cold"    # parked in the host tier
        assert "b" in mux.host_models()
        assert cb.hbm_grows >= 1 and cb.pool.n_pages > 4
        assert arb.evictions_forced >= 1
        assert arb.verify() == {}
        swap_ins0, rebuilds0 = mux.swap_ins, mux.cold_rebuilds
        lease = mux.acquire("b", timeout=60)  # squeeze KV, promote B
        try:
            assert mux.swap_ins == swap_ins0 + 1      # promoted bytes,
            assert mux.cold_rebuilds == rebuilds0     # not a rebuild
            out = float(np.asarray(fwd(b.device_params)))
            assert out == ref_out             # weights bit-exact after
            assert arb.verify() == {}         # the round trip
        finally:
            lease.release()
    finally:
        cb.shutdown()
        mux.close()


# -- protection + no-livelock -------------------------------------------------

def test_leased_and_pinned_models_never_victimized(lm):
    """A KV burst cannot evict a leased (or pinned) model: the grow
    probes find nothing reclaimable and the burst degrades to the
    pre-arbiter static path — queueing on its current pool — while the
    model stays hot and attached."""
    b = _Servable(words=4 * PN // 4)
    arb = HBMArbiter(8 * PN + PN // 2, measure_scratch=False)
    cb = _batcher(lm, arb, lanes=2, max_len=24, n_pages=4)
    mux = WeightMultiplexer(b.param_bytes(), hbm=arb)
    mux.register("b", _Adapter(b))
    try:
        lease = mux.acquire("b", timeout=10)
        try:
            futs = [cb.submit((np.arange(8) * (i + 1) % 64).astype(
                np.int32), 12) for i in range(2)]
            for f in futs:
                f.result(timeout=120)         # completes WITHOUT eviction
            assert mux.evictions == 0
            assert mux.state_of("b") == "hot"
            assert b.device_params is not None
            assert cb.pool.n_pages == 4       # static-budget behavior
        finally:
            lease.release()
        # pinned: same guarantee without any lease held
        mux.pin("b")
        f = cb.submit(np.arange(8, dtype=np.int32), 12)
        f.result(timeout=120)
        assert mux.evictions == 0 and mux.state_of("b") == "hot"
        assert arb.verify() == {}
    finally:
        cb.shutdown()
        mux.close()


def test_high_priority_inflight_lane_never_victimized(lm):
    """Pressure preempts the coldest-priority lane first and STOPS once
    the target is covered — the higher-priority in-flight decode keeps
    its pages and its stream; both streams finish bit-exact.

    Layout is deterministic by construction: each request's whole
    footprint fits its prefill pages (decode positions stay inside the
    last prompt page), so the high-priority lane holds the LOW page ids
    (admitted first, prefer-low allocation) and the low-priority victim
    holds exactly the ids a shrink can drop."""
    hi_prompt = np.arange(2, 22, dtype=np.int32) % 64   # 20 tokens
    lo_prompt = (np.arange(20, dtype=np.int32) * 7) % 64
    steps = 4                                 # positions 20..23: page 3
    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=1, lanes=2,
                               max_len=24, page_size=8, n_pages=12,
                               compute_dtype=jnp.float32)
    try:
        rhi = [int(t) for t in
               ref_cb.submit(hi_prompt, steps, priority=5).result(120)]
        rlo = [int(t) for t in
               ref_cb.submit(lo_prompt, steps).result(timeout=120)]
    finally:
        ref_cb.shutdown()

    b = _Servable(words=6 * PN // 4, resident=False)  # needs 6 pages
    arb = HBMArbiter(10 * PN + PN // 2, measure_scratch=False)
    cb = _batcher(lm, arb, lanes=2, max_len=24, n_pages=10,
                  decode_block=1)
    mux = WeightMultiplexer(b.param_bytes(), hbm=arb)
    mux.register("b", _Adapter(b), params=b.rebuild())
    try:
        sync = [threading.Event(), threading.Event()]

        def _tok(k):
            def hook(t, i):
                sync[k].set()
                time.sleep(0.05)  # keep both streams alive through
                #                   the squeeze window
            return hook

        fhi = cb.submit(hi_prompt, steps, priority=5, on_token=_tok(0))
        assert sync[0].wait(60)               # hi fully prefilled: pages
        flo = cb.submit(lo_prompt, steps,     # 1-3; lo lands on 4-6
                        on_token=_tok(1))
        assert sync[1].wait(60)
        lease = mux.acquire("b", timeout=60)  # needs the lo lane's pages
        try:
            assert cb.hbm_demotions >= 1      # the lo lane was demoted
            # exactly ONE victim — pressure stopped at the target; the
            # high-priority lane was never preempted (still decoding or
            # already done, its pages untouched)
            assert cb.preemptions == 1
            with cb._cv:
                active = [r for r in cb._active if r is not None]
            assert (any(r.future is fhi for r in active)
                    or fhi.done())
        finally:
            lease.release()
        assert [int(t) for t in fhi.result(timeout=120)] == rhi
        assert [int(t) for t in flo.result(timeout=120)] == rlo
        assert arb.verify() == {}
    finally:
        cb.shutdown()
        mux.close()


def test_no_livelock_when_both_tenants_at_budget():
    """Both tenants at budget with nothing reclaimable: a blocking
    request DENIES within the barren-round bound (never spins to the
    timeout), counts the denial, and leaves the ledger untouched."""
    arb = HBMArbiter(1024, measure_scratch=False)
    arb.register("kv", reclaim=lambda n: 0, gauge=lambda: 1024)
    arb.claim("kv", "pool", 1024)
    t0 = time.monotonic()
    assert not arb.request("weights", "m", 512, timeout=30.0)
    assert time.monotonic() - t0 < 5.0        # barren rounds, not timeout
    assert arb.denials == 1
    assert arb.ledger.claims() == [("kv", "pool", 1024)]
    assert arb.verify() == {}


# -- chaos: hbm.pressure ------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("action", ["error", "drop"])
def test_chaos_pressure_degrades_to_static_budget(lm, action):
    """Chaos at the arbiter's decision sites suppresses cross-tenant
    pressure: the acquire falls back to the mux's own static budget (the
    pre-arbiter behavior), the KV pool is never squeezed, and the ledger
    stays exactly consistent with the gauges — degraded means a skipped
    optimization, never corrupt accounting."""
    b = _Servable(words=4 * PN // 4, resident=False)
    arb = HBMArbiter(5 * PN, measure_scratch=False)  # B needs KV's bytes
    cb = _batcher(lm, arb, lanes=1, max_len=24, n_pages=4)
    mux = WeightMultiplexer(b.param_bytes(), hbm=arb)
    mux.register("b", _Adapter(b), params=b.rebuild())
    try:
        with chaos.inject(f"hbm.pressure={action}") as sched:
            lease = mux.acquire("b", timeout=20)
            lease.release()
        assert sched.fired("hbm.pressure") >= 1
        assert mux.state_of("b") == "hot"     # served via the static path
        assert cb.pool.n_pages == 4           # KV never squeezed
        assert cb.hbm_shrinks == 0 and cb.hbm_demotions == 0
        assert arb.denials >= 1               # the arbiter said no
        assert arb.verify() == {}             # ledger mirrors the
        #                                       over-committed truth exactly
        assert arb.free_hbm_bytes < 0         # honest over-commit report
    finally:
        cb.shutdown()
        mux.close()


# -- scratch tenant -----------------------------------------------------------

def test_compiled_scratch_recorded_per_jit(lm):
    """With measure_scratch on, every fused program the batcher compiles
    records a ("scratch", (name, signature)) ledger claim from the XLA
    compile-time memory analysis — the third tenant admission never saw
    before — and the claims survive verify()."""
    arb = HBMArbiter(1 << 30)                 # roomy: scratch discovery
    cb = _batcher(lm, arb, lanes=1, max_len=24, n_pages=4)
    try:
        cb.submit(np.arange(8, dtype=np.int32), 8).result(timeout=120)
        # the round that carried the prompt and the decode block behind it
        assert arb.ledger.tenant_claims(SCRATCH_TENANT) >= 2
        names = {tag[0] for (t, tag, _n) in arb.ledger.claims()
                 if t == SCRATCH_TENANT}
        assert "paged_mixed_step" in names
        assert "paged_decode_block" in names
        assert arb.ledger.tenant_bytes(SCRATCH_TENANT) >= 0
        assert arb.verify() == {}             # kv gauge still byte-exact
        # headroom subtracts scratch next to pool bytes — one honest sum
        assert (arb.free_hbm_bytes
                == arb.capacity_bytes - arb.ledger.total_claimed)
    finally:
        cb.shutdown()


# -- admission + Status RPC ---------------------------------------------------

def test_admission_consults_unified_headroom():
    from tpulab.serving import AdmissionConfig, AdmissionController

    class _Pool:
        page_size = 8
        page_nbytes = PN
        free_pages = 0

    class _Eng:
        pool = _Pool()
        page_size = 8
        lanes = 4
        active_lanes = 0
        queued_requests = 0

    arb = HBMArbiter(4 * PN, measure_scratch=False)
    arb.claim("kv", "pool", 4 * PN)           # no free headroom
    ctrl = AdmissionController(AdmissionConfig(max_inflight=4),
                               load=_Eng(), hbm=arb)
    # zero free pages + zero ledger headroom + nothing reclaimable: deny
    assert not ctrl._capacity_ok_locked(cost=16)
    # an evictable cold model elsewhere IS capacity under the economy
    arb.register("weights", reclaimable=lambda: 2 * PN)
    assert ctrl._capacity_ok_locked(cost=16)
    assert not ctrl._capacity_ok_locked(cost=2 * 8 * 2 + 1)  # beyond it
    # freeing ledger headroom moves the same single number: 2 pages free
    # + 2 pages reclaimable = 32 admissible tokens
    arb.ledger.resize("kv", "pool", 2 * PN)
    assert ctrl._capacity_ok_locked(cost=4 * 8)
    assert not ctrl._capacity_ok_locked(cost=4 * 8 + 1)


def test_status_and_poll_load_report_free_hbm(lm):
    """The Status RPC carries the single arbiter headroom next to
    free_kv_pages, and poll_load parses it."""
    import tpulab
    from tpulab.rpc.replica import ReplicaSet

    arb = HBMArbiter(64 * PN, measure_scratch=False)
    cb = _batcher(lm, arb, lanes=1, max_len=24, n_pages=4)
    mgr = tpulab.InferenceManager(max_exec_concurrency=2)
    try:
        mgr.serve(port=0, generation_engines={"llm": cb}, hbm=arb)
        addr = f"localhost:{mgr.server.bound_port}"
        rs = ReplicaSet([addr], "llm")
        try:
            load = rs.poll_load()
            assert load[addr]["free_hbm_bytes"] == arb.free_hbm_bytes
            assert load[addr]["free_hbm_bytes"] > 0
            assert load[addr]["free_kv_pages"] == cb.pool.free_pages
        finally:
            for m in rs._managers:
                m.close()
    finally:
        mgr.shutdown()
        cb.shutdown()


# -- telemetry ----------------------------------------------------------------

def test_hbm_metrics_poll():
    pytest.importorskip("prometheus_client")
    from tpulab.utils.metrics import HBMMetrics

    arb = HBMArbiter(4096, measure_scratch=False)
    arb.register("kv", reclaim=lambda n: 0, gauge=lambda: 3072)
    arb.claim("kv", "pool", 3072)
    assert arb.request("weights", "m", 512, timeout=1.0)
    assert not arb.request("weights", "m2", 4096, timeout=0.5)
    m = HBMMetrics()
    m.poll(arb)
    val = m.registry.get_sample_value
    assert val("tpulab_hbm_capacity_bytes") == 4096
    assert val("tpulab_hbm_headroom_bytes") == 4096 - 3072 - 512
    assert val("tpulab_hbm_tenant_bytes", {"tenant": "kv"}) == 3072
    assert val("tpulab_hbm_tenant_bytes", {"tenant": "weights"}) == 512
    assert val("tpulab_hbm_tenant_claims", {"tenant": "kv"}) == 1
    assert val("tpulab_hbm_grants_total") == 1
    assert val("tpulab_hbm_denials_total") == 1
    assert val("tpulab_hbm_pressure_events_total") >= 1
    m.poll(arb)                               # idempotent re-poll
    assert val("tpulab_hbm_denials_total") == 1


# -- elastic pool unit --------------------------------------------------------

def test_pool_grow_shrink_tracked_bytes():
    pool = PagedKVPool(4, 8, 1, 2, 16, jnp.float32)
    pn = pool.page_nbytes
    assert pool.hbm_bytes == 4 * pn
    # prefer-low allocation packs the bottom, keeping the top shrinkable
    a, b = pool.allocate_page(), pool.allocate_page()
    assert (a, b) == (1, 2)
    assert pool.shrinkable_pages() == 1       # only page 3 is top-free
    assert pool.grow(4) == 4
    assert pool.n_pages == 8 and pool.hbm_bytes == 8 * pn
    assert pool.free_pages == 5
    # shrink drops only contiguously free TOP ids — never live pages
    assert pool.shrink(8) == 5
    assert pool.n_pages == 3 and pool.hbm_bytes == 3 * pn
    assert pool.refcount(a) == 1 and pool.refcount(b) == 1
    assert pool.shrink(8) == 0                # nothing shrinkable left
    pool.release_pages([a, b])
    assert pool.shrink(8) == 2                # page 0 always survives
    assert pool.n_pages == 1
    pool.close()


# -- elastic pool under a mesh (PR 11 follow-up, closed as a contract) --------
def test_arbiter_armed_batcher_rejects_mesh():
    """An arbiter-armed (elastic) pool under a mesh has NO silent
    corruption path: grow/shrink per-shard accounting is untested, so
    construction rejects with a clear NotImplementedError (ROADMAP
    item 3 is where per-axis claims land)."""
    from tpulab.parallel import make_mesh
    lm2 = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                  n_layers=1, d_ff=64)
    arb = HBMArbiter(64 * PN, measure_scratch=False)
    mesh = make_mesh({"model": 2}, jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="mesh"):
        ContinuousBatcher(lm2, n_heads=2, n_layers=1, lanes=2,
                          max_len=24, page_size=8, n_pages=4,
                          compute_dtype=jnp.float32, hbm=arb, mesh=mesh)
    # the arbiter saw no tenant registration / claims from the aborted
    # construction (a half-registered tenant would wedge later arming)
    assert arb.ledger.total_claimed == 0
    assert arb.verify() == {}


def test_mesh_pool_grow_shrink_accounting_without_arbiter():
    """The pool-level grow/shrink ops themselves keep exact LOGICAL and
    per-shard byte accounting under a mesh (the primitive the future
    per-axis arbiter will build on): page ids stay stable, per-shard
    bytes stay hbm_bytes/n_shards, and freed ids come off the top."""
    from tpulab.parallel import make_mesh
    mesh = make_mesh({"model": 2}, jax.devices()[:2])
    pool = PagedKVPool(5, 8, 1, 2, 16, jnp.float32, mesh=mesh)
    try:
        pn0 = pool.page_nbytes
        assert pool.hbm_bytes == 5 * pn0
        assert pool.hbm_bytes_per_shard * pool.n_shards == pool.hbm_bytes
        held = [pool.allocate_page() for _ in range(2)]
        assert pool.grow(3) == 3
        assert pool.n_pages == 8 and pool.page_nbytes == pn0
        assert pool.hbm_bytes == 8 * pn0
        assert pool.hbm_bytes_per_shard * pool.n_shards == pool.hbm_bytes
        # new top ids are allocatable; the held ids were never remapped
        top = {pool.allocate_page() for _ in range(pool.free_pages)}
        assert set(range(5, 8)) <= top and not (top & set(held))
        pool.release_pages(list(top))
        assert pool.shrink(3) == 3  # the grown top is contiguously free
        assert pool.n_pages == 5 and pool.hbm_bytes == 5 * pn0
        assert pool.hbm_bytes_per_shard * pool.n_shards == pool.hbm_bytes
        pool.release_pages(held)
        assert pool.free_pages == 4  # page 0 stays reserved scratch
    finally:
        pool.close()
