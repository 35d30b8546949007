"""Admission control & QoS (tpulab/serving/, docs/SERVING.md): bounded
queues, per-tenant fair scheduling, and overload fast-fail for the
serving frontend.  Covers the acceptance contract: at overload the server
fast-fails with RESOURCE_EXHAUSTED + retry_after_ms instead of queueing
unboundedly, sheds strictly lowest-priority-first, a throttled tenant
still completes against a greedy one, rejected requests consume no
lanes/pages, and the default-off path is unchanged."""

import threading
import time

import numpy as np
import pytest

from helpers_engine import join_all, wait_until
from tpulab.core.deadline import Deadline
from tpulab.serving import (AdmissionConfig, AdmissionController,
                            AdmissionRejected, DeficitRoundRobinQueue,
                            TokenBucket)


# ---------------------------------------------------------------- units ----
def test_token_bucket_refill_and_retry_hint():
    clk = [0.0]
    b = TokenBucket(2.0, clock=lambda: clk[0])  # burst defaults to rate (2)
    assert b.try_take() and b.try_take()
    assert not b.try_take()
    assert b.retry_after_s() == pytest.approx(0.5)
    clk[0] += 0.5
    assert b.try_take()
    clk[0] += 100.0  # refill caps at burst
    assert b.try_take() and b.try_take() and not b.try_take()
    with pytest.raises(ValueError):
        TokenBucket(0.0)


class _Item:
    def __init__(self, tenant, cost=1, priority=0, seq=0):
        self.tenant, self.cost, self.priority, self.seq = (tenant, cost,
                                                           priority, seq)


def test_drr_queue_interleaves_tenants_and_sheds_lowest():
    q = DeficitRoundRobinQueue(quantum=10)
    for i in range(6):
        q.push(_Item("greedy", cost=10, seq=i))
    for i in range(2):
        q.push(_Item("slow", cost=10, seq=100 + i))
    order = [q.pop().tenant for _ in range(len(q))]
    # the slow tenant is served within the first round, not behind the
    # greedy tenant's whole backlog — the non-starvation contract
    assert "slow" in order[:3], order
    assert order.count("slow") == 2
    # shed candidate: globally lowest priority, youngest arrival in ties
    q2 = DeficitRoundRobinQueue()
    a, b, c = (_Item("x", priority=5, seq=1), _Item("x", priority=0, seq=2),
               _Item("y", priority=0, seq=3))
    for it in (a, b, c):
        q2.push(it)
    v = q2.peek_lowest_priority()
    assert v is c  # priority 0 tie -> youngest (seq 3)
    assert q2.remove(v) and not q2.remove(v)
    assert len(q2) == 2


def test_drr_cost_weighting_favors_cheap_tenant():
    """DRR is COST-weighted: a tenant of 1-cost requests drains several
    per round while a 30-cost tenant waits for deficit to accumulate."""
    q = DeficitRoundRobinQueue(quantum=10)
    for i in range(6):
        q.push(_Item("cheap", cost=1, seq=i))
    for i in range(3):
        q.push(_Item("pricey", cost=30, seq=10 + i))
    first_six = [q.pop().tenant for _ in range(6)]
    assert first_six.count("cheap") >= 4, first_six


def test_admission_bounded_queue_fast_fails_with_retry_hint():
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               max_queue_depth=1,
                                               expected_service_s=0.2))
    t0 = ctrl.admit("a")  # fast path
    assert t0.queue_wait_s == 0.0
    held = []
    th = threading.Thread(
        target=lambda: held.append(ctrl.admit("b")))
    th.start()
    for _ in range(100):
        if ctrl.queue_depth == 1:
            break
        time.sleep(0.01)
    assert ctrl.queue_depth == 1
    # the bounded queue is full: an equal-priority arrival fast-fails
    # with reason + retry-after hint instead of queueing unboundedly
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit("c")
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_ms > 0
    assert ctrl.peak_queue_depth == 1
    t0.release()  # dispatches the queued waiter
    th.join(timeout=10)
    assert held and held[0].queue_wait_s >= 0.0
    held[0].release()
    assert ctrl.admitted_total == 2
    assert ctrl.rejected_by_reason == {"queue_full": 1}


def test_admission_sheds_strictly_lowest_priority_first():
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               max_queue_depth=2))
    blocker = ctrl.admit("hold")
    outcomes = {}
    lock = threading.Lock()

    def waiter(name, prio):
        try:
            t = ctrl.admit(name, priority=prio)
            with lock:
                outcomes[name] = "admitted"
            t.release()
        except AdmissionRejected as e:
            with lock:
                outcomes[name] = e.reason

    ths = [threading.Thread(target=waiter, args=(f"p{p}", p))
           for p in (1, 2)]
    for t in ths:
        t.start()
        time.sleep(0.05)
    for _ in range(100):
        if ctrl.queue_depth == 2:
            break
        time.sleep(0.01)
    # queue = [p1, p2]; a p3 arrival sheds p1 (the lowest), then a p4
    # arrival sheds p2 — strictly lowest-priority-first
    ths += [threading.Thread(target=waiter, args=("p3", 3))]
    ths[-1].start()
    for _ in range(100):
        if outcomes.get("p1"):
            break
        time.sleep(0.01)
    assert outcomes.get("p1") == "shed"
    ths += [threading.Thread(target=waiter, args=("p4", 4))]
    ths[-1].start()
    for _ in range(100):
        if outcomes.get("p2"):
            break
        time.sleep(0.01)
    assert outcomes.get("p2") == "shed"
    # an arrival that does NOT outrank the lowest queued request is
    # itself rejected — it cannot shed its way in
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit("p0", priority=0)
    assert ei.value.reason == "queue_full"
    blocker.release()
    for t in ths:
        t.join(timeout=10)
    assert outcomes["p3"] == "admitted" and outcomes["p4"] == "admitted"
    assert ctrl.shed_total == 2


def test_admission_deadline_aware_early_reject():
    """Predicted queue wait > remaining deadline -> reject immediately,
    without queueing (no decode steps burned on a doomed request)."""
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               max_queue_depth=8,
                                               expected_service_s=1.0))
    blocker = ctrl.admit("hold")
    th = threading.Thread(target=lambda: ctrl.admit("queued").release())
    th.start()
    for _ in range(100):
        if ctrl.queue_depth == 1:
            break
        time.sleep(0.01)
    with pytest.raises(AdmissionRejected) as ei:
        # predicted wait ~= (1 queued + 1) * 1.0s / 1 = 2s >> 50ms budget
        ctrl.admit("late", deadline=Deadline.after(0.05))
    assert ei.value.reason == "deadline"
    assert ctrl.queue_depth == 1  # never entered the queue
    # an unbounded request still queues happily under the same pressure
    blocker.release()
    th.join(timeout=10)


def test_admission_fair_queue_non_starvation():
    """One greedy tenant cannot starve a slow one: with DRR dispatch the
    slow tenant's request is served within the first round instead of
    behind the greedy backlog."""
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               max_queue_depth=16))
    blocker = ctrl.admit("warm")
    order = []
    lock = threading.Lock()

    def worker(tenant):
        t = ctrl.admit(tenant, cost=10)
        with lock:
            order.append(tenant)
        t.release()  # immediately hand capacity to the next dispatch

    ths = []
    for _ in range(5):  # greedy enqueues its backlog first
        ths.append(threading.Thread(target=worker, args=("greedy",)))
        ths[-1].start()
        wait_until(lambda: ctrl.queue_depth >= len(ths),
                   "a greedy waiter queued")
    ths.append(threading.Thread(target=worker, args=("slow",)))
    ths[-1].start()
    wait_until(lambda: ctrl.queue_depth >= len(ths), "the slow waiter queued")
    blocker.release()
    join_all(ths, timeout_s=10)
    assert order.count("slow") == 1
    assert "slow" in order[:2], order  # served in round 1, not position 6


def test_admission_rate_limits_global_and_per_tenant():
    ctrl = AdmissionController(AdmissionConfig(max_inflight=8,
                                               tenant_rate=1.0))
    ctrl.admit("a").release()  # burst of 1: tenant a's budget spent
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit("a")
    assert ei.value.reason == "tenant_rate"
    assert ei.value.retry_after_ms > 0
    ctrl.admit("b").release()  # another tenant's bucket is untouched
    g = AdmissionController(AdmissionConfig(max_inflight=8,
                                            global_rate=1.0))
    g.admit("a").release()
    with pytest.raises(AdmissionRejected) as ei:
        g.admit("b")  # global bucket spans tenants
    assert ei.value.reason == "global_rate"


def test_admission_chaos_trip_point():
    """serving.admission (docs/ROBUSTNESS.md): an armed error rule forces
    the overload path — a synthetic RESOURCE_EXHAUSTED rejection."""
    from tpulab import chaos
    ctrl = AdmissionController(AdmissionConfig(max_inflight=8))
    with chaos.inject("serving.admission=error+1") as sched:
        with pytest.raises(AdmissionRejected) as ei:
            ctrl.admit("t")
        assert ei.value.reason == "chaos"
        assert sched.fired("serving.admission") == 1
        ctrl.admit("t").release()  # rule exhausted: admission is clean
    assert ctrl.rejected_by_reason == {"chaos": 1}


def test_admission_metrics_export():
    from prometheus_client import CollectorRegistry

    from tpulab.utils.metrics import AdmissionMetrics
    m = AdmissionMetrics(registry=CollectorRegistry())
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               max_queue_depth=0),
                               metrics=m)
    ctrl.admit("team-a").release()
    hold = ctrl.admit("team-a")
    with pytest.raises(AdmissionRejected):
        ctrl.admit("team-b")
    hold.release()

    def sample(name, labels=None):
        return m.registry.get_sample_value(name, labels or {})

    assert sample("tpulab_admission_admitted_total",
                  {"tenant": "team-a"}) == 2
    assert sample("tpulab_admission_rejected_total",
                  {"reason": "queue_full", "tenant": "team-b"}) == 1
    assert sample("tpulab_admission_queue_wait_seconds_count") == 2
    assert sample("tpulab_admission_inflight") == 0


# ------------------------------------------------------------- e2e gRPC ----
def _paced_dense_engine(delay_s=0.02):
    """A max_sessions=1 dense engine whose stream is paced, so overload
    is deterministic to provoke."""
    import jax.numpy as jnp

    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=48)
    eng = GenerationEngine(params, n_heads=2, n_layers=1, max_len=64,
                           max_sessions=1, compute_dtype=jnp.float32)

    class Paced:
        vocab = 64

        def start_session(self, timeout=None):
            import contextlib
            cm = eng.start_session(timeout=timeout)

            @contextlib.contextmanager
            def wrap():
                with cm as sess:
                    class S:
                        prefill = staticmethod(sess.prefill)

                        @staticmethod
                        def stream(steps):
                            for tok in sess.stream(steps):
                                time.sleep(delay_s)
                                yield tok
                    yield S()
            return wrap()
    return Paced()


def _serve_gen(engine, admission=None, metrics=None):
    import tpulab
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.serve(port=0, generation_engines={"lm": engine},
              admission=admission)
    return mgr


def test_overload_burst_fast_fails_with_retry_after():
    """The acceptance burst: at well over capacity the server fast-fails
    with RESOURCE_EXHAUSTED + retry_after_ms instead of queueing
    unboundedly, and serves normally after the storm."""
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager,
                                          ResourceExhausted)
    adm = AdmissionController(AdmissionConfig(max_inflight=1,
                                              max_queue_depth=1,
                                              expected_service_s=0.5))
    mgr = _serve_gen(_paced_dense_engine(), admission=adm)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        results = []
        lock = threading.Lock()

        def run():
            try:
                toks = list(GenerateStreamClient(remote, "lm").generate(
                    np.arange(4, dtype=np.int32), 8))
                with lock:
                    results.append(("ok", len(toks)))
            except ResourceExhausted as e:
                with lock:
                    results.append(("rex", e.retry_after_ms))

        ths = [threading.Thread(target=run) for _ in range(6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        oks = [r for r in results if r[0] == "ok"]
        rex = [r for r in results if r[0] == "rex"]
        assert len(oks) + len(rex) == 6, results
        assert len(oks) >= 1 and len(rex) >= 3, results
        assert all(n == 8 for _, n in oks)
        assert all(ms > 0 for _, ms in rex), "retry_after_ms hint missing"
        # bounded queueing is the whole point: depth never exceeded the cap
        assert adm.peak_queue_depth <= 1
        assert adm.rejected_by_reason.get("queue_full", 0) >= 3
        # recovery: post-storm traffic is served cleanly
        toks = list(GenerateStreamClient(remote, "lm").generate(
            np.arange(4, dtype=np.int32), 4))
        assert len(toks) == 4
    finally:
        remote.close()
        mgr.shutdown()


def test_rejected_request_frees_no_lanes_or_pages():
    """An admission-rejected request must be turned away BEFORE touching
    the batcher: no lane occupancy, no page churn, no queued residue."""
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager,
                                          ResourceExhausted)
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=48)
    cb = ContinuousBatcher(params, n_heads=2, n_layers=1, lanes=1,
                           max_len=32, page_size=8,
                           compute_dtype=jnp.float32)
    adm = AdmissionController(AdmissionConfig(max_inflight=1,
                                              max_queue_depth=0),
                              load=cb)
    mgr = _serve_gen(cb, admission=adm)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        free0 = cb.pool.free_pages
        results = []
        lock = threading.Lock()

        def run():
            try:
                toks = list(GenerateStreamClient(remote, "lm").generate(
                    np.arange(4, dtype=np.int32), 6))
                with lock:
                    results.append(("ok", len(toks)))
            except ResourceExhausted as e:
                with lock:
                    results.append(("rex", e.retry_after_ms))

        ths = [threading.Thread(target=run) for _ in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        oks = [r for r in results if r[0] == "ok"]
        rex = [r for r in results if r[0] == "rex"]
        assert oks and rex, results
        # rejected requests never reached the batcher: every submission
        # that DID reach it completed, nothing is queued, pages restored
        assert cb.completed_requests == len(oks)
        assert cb.queued_requests == 0 and cb.active_lanes == 0
        for _ in range(100):
            if cb.pool.free_pages == free0:
                break
            time.sleep(0.01)  # last tick may still be releasing
        assert cb.pool.free_pages == free0
        # Status RPC exports the load gauges the routers read
        st = remote.server_status()
        assert st.free_kv_pages == free0
        assert st.queued_requests == 0
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()


def test_two_tenant_fairness_throttled_tenant_completes():
    """A greedy tenant saturating the frontend cannot starve a slow one:
    the slow tenant's requests ride the DRR queue and complete while the
    greedy backlog is still draining."""
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)
    adm = AdmissionController(AdmissionConfig(max_inflight=1,
                                              max_queue_depth=16))
    mgr = _serve_gen(_paced_dense_engine(delay_s=0.01), admission=adm)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        greedy_done, errors = [], []
        lock = threading.Lock()

        def greedy(i):
            try:
                list(GenerateStreamClient(remote, "lm").generate(
                    np.arange(4, dtype=np.int32), 8, tenant_id="greedy"))
                with lock:
                    greedy_done.append(i)
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(repr(e))

        ths = [threading.Thread(target=greedy, args=(i,)) for i in range(8)]
        for t in ths:
            t.start()
        # wait until the greedy tenant has actually built a backlog
        for _ in range(200):
            if adm.queue_depth >= 4:
                break
            time.sleep(0.01)
        assert adm.queue_depth >= 4
        toks = list(GenerateStreamClient(remote, "lm").generate(
            np.arange(4, dtype=np.int32), 8, tenant_id="slow"))
        with lock:
            greedy_at_slow_done = len(greedy_done)
        assert len(toks) == 8  # the throttled tenant completed...
        # ...while most of the greedy backlog was still pending (DRR let
        # it jump the greedy queue, not wait behind all 8)
        assert greedy_at_slow_done <= 6, greedy_at_slow_done
        for t in ths:
            t.join(timeout=120)
        assert not errors, errors
        assert len(greedy_done) == 8
    finally:
        remote.close()
        mgr.shutdown()


def test_admission_default_off_behavior_unchanged():
    """Default-off contract: without an AdmissionController the service
    has no admission state and a concurrent burst serves every request
    (blocking-lease backpressure, exactly the pre-subsystem behavior)."""
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)
    mgr = _serve_gen(_paced_dense_engine(delay_s=0.005))
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        assert mgr.server._infer_resources.admission is None
        results = []
        lock = threading.Lock()

        def run():
            toks = list(GenerateStreamClient(remote, "lm").generate(
                np.arange(4, dtype=np.int32), 5))
            with lock:
                results.append(len(toks))

        ths = [threading.Thread(target=run) for _ in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert results == [5, 5, 5, 5]  # nothing shed, nothing rejected
    finally:
        remote.close()
        mgr.shutdown()


# ------------------------------------------------- replica-set behavior ----
def test_resource_exhausted_not_a_breaker_fault_routes_away():
    """Satellite: RESOURCE_EXHAUSTED never counts toward the breaker
    streak — the overloaded replica stays closed and traffic routes to
    the healthy one with backoff."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.replica import ReplicaSet

    def serve(admission=None):
        mgr = tpulab.InferenceManager(max_exec_concurrency=1, max_buffers=4)
        mgr.register_model("mnist", make_mnist(max_batch_size=2))
        mgr.update_resources()
        mgr.serve(port=0, admission=admission)
        return mgr

    X = np.zeros((1, 28, 28, 1), np.float32)
    reject_all = AdmissionController(AdmissionConfig(max_inflight=0,
                                                     max_queue_depth=0))
    mgr_a, mgr_b = serve(admission=reject_all), serve()
    rs = None
    try:
        addrs = [f"127.0.0.1:{m.server.bound_port}" for m in (mgr_a, mgr_b)]
        rs = ReplicaSet(addrs, "mnist", breaker_threshold=1)
        for _ in range(6):
            out = rs.infer(Input3=X).result(timeout=60)
            assert out["Plus214_Output_0"].shape == (1, 10)
        assert all(s == "closed" for s in rs.breaker_states().values())
        assert rs.ejections == 0
        assert rs.overloads >= 1  # the overload was seen, noted, routed away
        assert rs.served == [0, 6]  # every completion on the healthy replica
    finally:
        if rs is not None:
            rs.close()
        mgr_a.shutdown()
        mgr_b.shutdown()


def test_single_overloaded_replica_honors_retry_after_then_fails():
    """All-replicas-overloaded: the set sleeps one jittered retry-after
    round, re-spreads, and only then surfaces ResourceExhausted — with
    the hint intact for the caller's own backoff."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import ResourceExhausted
    from tpulab.rpc.replica import ReplicaSet

    X = np.zeros((1, 28, 28, 1), np.float32)
    reject_all = AdmissionController(AdmissionConfig(max_inflight=0,
                                                     max_queue_depth=0))
    mgr = tpulab.InferenceManager(max_exec_concurrency=1, max_buffers=4)
    mgr.register_model("mnist", make_mnist(max_batch_size=2))
    mgr.update_resources()
    mgr.serve(port=0, admission=reject_all)
    rs = None
    try:
        rs = ReplicaSet([f"127.0.0.1:{mgr.server.bound_port}"], "mnist",
                        breaker_threshold=1, overload_retries=1)
        t0 = time.monotonic()
        with pytest.raises(ResourceExhausted) as ei:
            rs.infer(Input3=X).result(timeout=60)
        assert time.monotonic() - t0 >= 0.01  # one backoff round happened
        assert ei.value.retry_after_ms >= 0
        assert rs.breaker_states().popitem()[1] == "closed"
        assert rs.ejections == 0 and rs.overloads >= 2
    finally:
        if rs is not None:
            rs.close()
        mgr.shutdown()


def test_generation_replicaset_overload_routes_away():
    from tpulab.rpc.replica import GenerationReplicaSet
    reject_all = AdmissionController(AdmissionConfig(max_inflight=0,
                                                     max_queue_depth=0))
    mgr_a = _serve_gen(_paced_dense_engine(delay_s=0.0),
                       admission=reject_all)
    mgr_b = _serve_gen(_paced_dense_engine(delay_s=0.0))
    grs = None
    try:
        addrs = [f"127.0.0.1:{m.server.bound_port}" for m in (mgr_a, mgr_b)]
        grs = GenerationReplicaSet(addrs, "lm", breaker_threshold=1)
        for _ in range(3):
            assert len(list(grs.generate(np.arange(4, dtype=np.int32),
                                         5))) == 5
        assert all(s == "closed" for s in grs.breaker_states().values())
        assert grs.ejections == 0 and grs.overloads >= 1
        assert grs.served[1] == 3 and grs.served[0] == 0
    finally:
        if grs is not None:
            grs.close()
        mgr_a.shutdown()
        mgr_b.shutdown()


def test_pick_prefers_reported_least_loaded_on_inflight_ties():
    """Satellite: on local-inflight ties the pick consults the last
    server-reported queued_requests (Status RPC load gauges) instead of
    pure round-robin; full ties still rotate."""
    from tpulab.rpc.replica import ReplicaSet
    rs = ReplicaSet(["127.0.0.1:1", "127.0.0.1:2", "127.0.0.1:3"], "m")
    try:
        rs._load_hint = [5, 0, 5]
        for _ in range(3):  # the hint pins the tie-break, rr can't rotate
            idx = rs._pick(frozenset())
            assert idx == 1
            rs._inflight[1] -= 1  # undo the pick's bump
        # equal hints: round-robin rotation returns
        rs._load_hint = [2, 2, 2]
        picked = set()
        for _ in range(3):
            idx = rs._pick(frozenset())
            picked.add(idx)
            rs._inflight[idx] -= 1
        assert picked == {0, 1, 2}
    finally:
        rs.close()


def test_poll_load_reads_status_gauges():
    from tpulab.rpc.replica import ReplicaSet
    adm = AdmissionController(AdmissionConfig(max_inflight=4))
    mgr = _serve_gen(_paced_dense_engine(), admission=adm)
    rs = None
    try:
        addr = f"127.0.0.1:{mgr.server.bound_port}"
        rs = ReplicaSet([addr], "lm")
        load = rs.poll_load()
        assert load[addr] == {"queued_requests": 0, "free_kv_pages": 0,
                              "free_hbm_bytes": 0,  # no arbiter served
                              "role": "unified",
                              "resident_models": [], "host_models": [],
                              # no prefix cache on a dense engine
                              "prefix_hits": 0, "prefix_lookups": 0,
                              "draining": False,  # serving normally
                              "inflight_requests": 0}  # drain observable
        assert rs._load_hint == [0]
    finally:
        if rs is not None:
            rs.close()
        mgr.shutdown()
