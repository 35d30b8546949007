"""Offline batch lane tests (tpulab.batch, docs/SERVING.md "Offline
batch lane"): manifest/sink roundtrips, spare-capacity gating,
batch-first preemption ordering, chaos-kill resume with zero re-decode,
admission-class semantics (strictly below online, DRR exemption,
queue-wait-EWMA exclusion — the autoscaler-interaction satellite), the
fleet batch-drain hook, and the RPC request_class end to end."""

import threading
import time

import numpy as np
import pytest

from helpers_engine import GatedSink, TokenGate, join_all, wait_until
from tpulab import chaos
from tpulab.batch import BatchJob, BatchScheduler, JSONLResultSink


@pytest.fixture(scope="module")
def lm():
    from tpulab.models.transformer import init_transformer_params
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)


def _batcher(lm, lanes=2, **kw):
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    kw.setdefault("max_len", 96)
    kw.setdefault("page_size", 8)
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=lanes,
                             compute_dtype=jnp.float32, **kw)


def _prompts(n, rng_seed=0, length=6):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(0, 64, (length,), np.int32) for _ in range(n)]


def _run_in_thread(sched, job):
    """``sched.run(job)`` on a feeder thread; the report lands in ``res``."""
    res = {}
    th = threading.Thread(
        target=lambda: res.update(sched.run(job, timeout_s=120)),
        daemon=True)
    th.start()
    return th, res


def _gated_sink(gate, tmp_path):
    """Every delivered token durable at once (``flush_every=1``), the item
    held at ``gate``'s token."""
    return GatedSink(gate, JSONLResultSink(str(tmp_path / "held.jsonl"),
                                           flush_every=1))


def _release_on_cancel(cb, gate):
    """A feeder that dies (chaos, drain) cancels its in-flight item, and the
    engine sweeps a cancel at its next tick boundary.  The held item is let
    go the moment its cancel is registered: it can neither run on to its end
    first nor keep the feeder waiting for a sweep the hold keeps away."""
    cancel = cb.cancel

    def cancel_then_release(fut):
        cancel(fut)
        gate.release()

    cb.cancel = cancel_then_release


# -- manifest + sink ----------------------------------------------------------

def test_batch_job_validation_and_manifest_roundtrip():
    job = BatchJob("j", [[1, 2], [3]], steps=4, temperature=0.5,
                   device_sampling=True, seed=7, stop_tokens=(9,),
                   priority=2, metadata={"kind": "eval"})
    doc = job.to_manifest()
    back = BatchJob.from_manifest(doc)
    assert back.to_manifest() == doc
    assert back.resumable  # device-sampled: (seed, position)-keyed
    assert not BatchJob("h", [[1]], steps=2, temperature=0.5).resumable
    with pytest.raises(ValueError):
        BatchJob("", [[1]], steps=1)
    with pytest.raises(ValueError):
        BatchJob("j", [], steps=1)
    with pytest.raises(ValueError):
        BatchJob("j", [[]], steps=1)
    with pytest.raises(ValueError):
        BatchJob("j", [[1]], steps=0)


def test_jsonl_sink_checkpoint_resume_and_reset(tmp_path):
    path = str(tmp_path / "r.jsonl")
    sink = JSONLResultSink(path, flush_every=2)
    for i, t in enumerate([5, 6, 7]):
        sink.append_token("j", 0, i, t)
    sink.flush()
    p = sink.load_progress("j")
    assert p[0].tokens == [5, 6, 7] and not p[0].done
    # a resume continues at the durable prefix; overlapping replayed
    # deltas are idempotent via their start offsets
    sink.append_token("j", 0, 2, 7)   # replayed flush overlap
    sink.append_token("j", 0, 3, 8)
    sink.mark_done("j", 0, 4)
    p = sink.load_progress("j")
    assert p[0].tokens == [5, 6, 7, 8] and p[0].done
    # reset voids delivered tokens (host-sampled restart)
    sink.append_token("j", 1, 0, 1)
    sink.flush()
    sink.mark_reset("j", 1)
    sink.append_token("j", 1, 0, 2)
    sink.flush()
    p = sink.load_progress("j")
    assert p[1].tokens == [2] and not p[1].done
    # torn trailing write (a kill mid-append): durable prefix survives
    with open(path, "a") as f:
        f.write('{"job": "j", "item": 0, "tok')
    p = sink.load_progress("j")
    assert p[0].tokens == [5, 6, 7, 8] and p[0].done
    # other jobs' records are invisible
    assert sink.load_progress("other") == {}


# -- scheduler: run / resume / gating ----------------------------------------

def test_scheduler_runs_job_bit_exact_and_idempotent(lm, tmp_path):
    cb = _batcher(lm)
    try:
        prompts = _prompts(3, rng_seed=1)
        ref = [cb.submit(p, 5).result(timeout=120) for p in prompts]
        sink = JSONLResultSink(str(tmp_path / "s.jsonl"), flush_every=2)
        sched = BatchScheduler(cb, sink=sink)
        rep = sched.run(BatchJob("j", prompts, steps=5), timeout_s=120)
        assert rep["interrupted"] is None and rep["items_done"] == 3
        assert [rep["results"][i] for i in range(3)] == ref
        # rerun: everything already done in the sink — zero decode work
        tg0 = cb.tokens_generated
        rep2 = sched.run(BatchJob("j", prompts, steps=5), timeout_s=120)
        assert rep2["items_done"] == 3 and cb.tokens_generated == tg0
        assert sched.jobs_done == 2
    finally:
        cb.shutdown()


def test_spare_capacity_gate_defers_to_online(lm, tmp_path):
    """With every lane held by online work the feeder must not submit —
    the gate defers (spare_denials) until the lanes idle."""
    cb = _batcher(lm, lanes=1)
    try:
        prompts = _prompts(2, rng_seed=2)
        ref = [cb.submit(p, 4).result(timeout=120) for p in prompts]
        sched = BatchScheduler(cb)
        held = TokenGate()
        online = cb.submit(prompts[0], 48, on_token=held)
        assert held.wait(timeout=60)  # the only lane is online's, and stays
        th, res = _run_in_thread(sched, BatchJob("g", prompts, steps=4))
        wait_until(lambda: sched.spare_denials > 0,
                   "the feeder's first deferral")
        assert sched.tokens_delivered == 0  # online held: nothing was fed
        held.release()
        online.result(timeout=120)
        join_all([th], timeout_s=120)
        assert res["items_done"] == 2
        assert [res["results"][i] for i in range(2)] == ref
    finally:
        cb.shutdown()


def test_online_arrival_preempts_batch_lane_first(lm, tmp_path):
    """Acceptance: an online burst preempts the mid-decode BATCH lane —
    not the other online lane — and the batch job still completes with
    bit-exact token parity vs an uncontended run (satellite 3)."""
    cb = _batcher(lm, lanes=2)
    try:
        prompts = _prompts(3, rng_seed=3)
        ref_batch = cb.submit(prompts[0], 40).result(timeout=120)
        ref_o2 = cb.submit(prompts[2], 4).result(timeout=120)
        batch_held, o1_held = TokenGate(3), TokenGate()
        sched = BatchScheduler(cb, sink=_gated_sink(batch_held, tmp_path))
        th, res = _run_in_thread(sched,
                                 BatchJob("p", [prompts[0]], steps=40))
        assert batch_held.wait(timeout=60)  # batch mid-decode, and held
        o1 = cb.submit(prompts[1], 40, on_token=o1_held)  # queued
        batch_held.release()
        # held at o1's first token, the batch lane 36 tokens from its end
        assert o1_held.wait(timeout=60)
        assert cb.active_lanes == 2
        p0, bp0 = cb.preemptions, cb.batch_preemptions
        # default-priority online arrival with both lanes busy: the
        # BATCH lane falls, the online lane is untouched
        o2 = cb.submit(prompts[2], 4)  # queued behind two held lanes
        o1_held.release()
        assert o2.result(timeout=120) == ref_o2
        assert cb.batch_preemptions - bp0 >= 1
        assert (cb.preemptions - p0) == (cb.batch_preemptions - bp0)
        o1.result(timeout=120)
        join_all([th], timeout_s=120)
        assert res["interrupted"] is None
        assert res["batch_preemptions"] >= 1
        assert res["results"][0] == ref_batch  # exact in-engine resume
    finally:
        cb.shutdown()


@pytest.mark.parametrize("action", ["error", "drop"])
def test_chaos_batch_run_kill_resumes_from_checkpoint(lm, tmp_path,
                                                      action):
    """Acceptance: a batch.run chaos kill mid-decode ends the run with
    delivered tokens durable; the next run resumes from the JSONL
    checkpoint with ZERO re-decode of delivered tokens and bit-exact
    output (device-sampled — the strong parity class)."""
    cb = _batcher(lm, lanes=1, decode_block=2)
    try:
        prompt = _prompts(1, rng_seed=4)[0]
        steps = 40
        job_kw = dict(steps=steps, temperature=0.8, device_sampling=True,
                      seed=99)
        ref = cb.submit(prompt, steps,
                        sampling=BatchJob("r", [prompt], **job_kw)
                        .sampling()).result(timeout=120)
        held = TokenGate(5)
        sink = _gated_sink(held, tmp_path)
        sched = BatchScheduler(cb, sink=sink)
        _release_on_cancel(cb, held)
        th, res = _run_in_thread(sched, BatchJob("k", [prompt], **job_kw))
        assert held.wait(timeout=60)  # mid-decode, and held
        with chaos.inject(f"batch.run={action}") as sched_chaos:
            join_all([th], timeout_s=120)
            assert sched_chaos.fired("batch.run") >= 1
        assert res["interrupted"] == action
        assert sched.interrupted_runs == 1
        prog = sink.load_progress("k")
        n_part = len(prog[0].tokens)
        assert 0 < n_part < steps and not prog[0].done
        assert prog[0].tokens == ref[:n_part]  # durable = delivered
        tg0 = cb.tokens_generated
        rep2 = sched.run(BatchJob("k", [prompt], **job_kw),
                         timeout_s=120)
        assert rep2["interrupted"] is None
        assert rep2["results"][0] == ref           # bit-exact resume
        assert rep2["tokens_resume_skipped"] == n_part
        # zero re-decode: only the remaining steps were generated
        assert cb.tokens_generated - tg0 == steps - n_part
    finally:
        cb.shutdown()


def test_host_sampled_interrupt_restarts_behind_reset(lm, tmp_path):
    """Host-sampled jobs are allowed (the lane never streams to a
    human) but their draw-order PRNG cannot resume: an interrupted item
    restarts from scratch behind an explicit reset record."""
    cb = _batcher(lm, lanes=1, decode_block=2)
    try:
        prompt = _prompts(1, rng_seed=5)[0]
        steps = 32
        held = TokenGate(4)
        sink = _gated_sink(held, tmp_path)
        sched = BatchScheduler(cb, sink=sink)
        _release_on_cancel(cb, held)
        job_kw = dict(steps=steps, temperature=0.9, top_k=4, seed=7)
        th, res = _run_in_thread(sched, BatchJob("h", [prompt], **job_kw))
        assert held.wait(timeout=60)  # mid-decode, and held
        with chaos.inject("batch.run=drop"):
            join_all([th], timeout_s=120)
        assert res["interrupted"] == "drop"
        lost = len(sink.load_progress("h")[0].tokens)
        assert lost > 0
        rep2 = sched.run(BatchJob("h", [prompt], **job_kw),
                         timeout_s=120)
        assert rep2["interrupted"] is None
        assert len(rep2["results"][0]) == steps  # full restart completed
        assert rep2["tokens_resume_skipped"] == 0
        assert sched.tokens_restart_lost == lost
        assert sink.load_progress("h")[0].done
    finally:
        cb.shutdown()


def test_pick_block_k_batch_lane_never_streaming_clamped(lm):
    """Throughput-optimized lane: a batch request's on_token hook is a
    checkpoint sink — it must NOT drag the fused block to the K<=2
    interactive clamp the way an online streaming consumer does."""
    from tpulab.engine.paged import _PagedRequest
    cb = _batcher(lm, decode_block=8)
    try:
        def mk(batch):
            r = _PagedRequest(np.asarray([1], np.int32), 16,
                              on_token=lambda *a: None, batch=batch)
            r.tokens_out = [1]
            return r
        assert cb._pick_block_k([(0, mk(batch=False))]) == 2
        assert cb._pick_block_k([(0, mk(batch=True))]) == 8
    finally:
        cb.shutdown()


# -- admission-class semantics ------------------------------------------------

def test_admission_batch_strictly_below_online_and_drr_exempt():
    """Batch waiters ride their OWN queue: no online queue slot, no
    online tenant deficit movement, and dispatch strictly after every
    online waiter even when the batch request arrived first."""
    from tpulab.serving.admission import (AdmissionConfig,
                                          AdmissionController)
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               admit_wait_s=10.0))
    order = []
    first = ctrl.admit("a")             # occupy the only slot

    def take(tag, **kw):
        with ctrl.admit(**kw):
            order.append(tag)
            time.sleep(0.02)

    tb = threading.Thread(target=take, args=("batch",),
                          kwargs=dict(tenant="bulk",
                                      request_class="batch"),
                          daemon=True)
    tb.start()                          # batch queues FIRST
    wait_until(lambda: ctrl.batch_queue_depth == 1, "the batch waiter queued")
    to = threading.Thread(target=take, args=("online",),
                          kwargs=dict(tenant="a"), daemon=True)
    to.start()
    wait_until(lambda: ctrl.queue_depth == 1, "the online waiter queued")
    # structural exemption: the online DRR queue never saw the batch
    # tenant; the debugz view namespaces it
    depths = ctrl.queue_depths()
    assert depths.get("batch:bulk") == 1 and depths.get("a") == 1
    assert ctrl._queue.deficit_of("bulk") == 0.0
    first.release()
    join_all([to, tb], timeout_s=10)
    assert order == ["online", "batch"]  # arrival order reversed
    assert ctrl.batch_admitted_total == 1


def test_admission_queue_wait_ewma_excludes_batch_and_autoscaler_holds():
    """Satellite: batch-class admissions never move queue_wait_ewma_s,
    so the FleetAutoscaler (whose wait trigger reads exactly that
    export) does not scale up under a pure batch flood."""
    from tpulab.fleet import FleetAutoscaler, ReplicaProvider
    from tpulab.serving.admission import (AdmissionConfig,
                                          AdmissionController)
    ctrl = AdmissionController(AdmissionConfig(max_inflight=1,
                                               admit_wait_s=10.0))
    waited = {}

    def queued_admit(request_class):
        first = ctrl.admit("a")
        done = threading.Event()

        def second():
            with ctrl.admit("b", request_class=request_class) as t:
                waited[request_class] = t.queue_wait_s
            done.set()

        threading.Thread(target=second, daemon=True).start()
        wait_until(lambda: ctrl.batch_queue_depth + ctrl.queue_depth == 1,
                   f"the {request_class} waiter queued")
        time.sleep(0.03)                # accrue a real queue wait
        first.release()
        assert done.wait(timeout=10)

    queued_admit("batch")
    assert waited["batch"] > 0.0        # it DID wait...
    assert ctrl.queue_wait_ewma_s == 0.0  # ...and the EWMA ignored it

    class FakeSet:
        addresses = ["a"]
        overloads = 0
        active_count = 1

        @property
        def inflight(self):
            return [0]

        def active_addresses(self):
            return ["a"]

        def load_hints(self):
            return {"a": 0}

        def add_replica(self, addr):
            raise AssertionError("scaled up on batch pressure")

    asc = FleetAutoscaler(FakeSet(), ReplicaProvider(),
                          wait_signal=lambda: ctrl.queue_wait_ewma_s,
                          up_wait_s=0.01, hold=1, min_replicas=1,
                          max_replicas=4)
    assert asc.evaluate() == ""         # no trigger from batch waits
    assert asc.scale_ups == 0
    # the SAME wait pattern online-class moves the EWMA (the control)
    queued_admit("online")
    assert ctrl.queue_wait_ewma_s > 0.0


def test_admission_batch_spare_gate_consults_engine_idle():
    """A busy load source (no idle lane / queued work) blocks batch
    dispatch outright while online admission still proceeds."""
    from tpulab.serving.admission import (AdmissionConfig,
                                          AdmissionController,
                                          AdmissionRejected)

    class BusyEngine:
        lanes = 2
        active_lanes = 2
        queued_requests = 0
        page_size = 8

    ctrl = AdmissionController(AdmissionConfig(max_inflight=4,
                                               admit_wait_s=0.15),
                               load=BusyEngine())
    with ctrl.admit("a"):               # online: lanes busy but capacity
        pass
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit("bulk", request_class="batch")
    assert ei.value.reason == "queue_timeout"
    BusyEngine.active_lanes = 0         # lanes idle: batch admits now
    with ctrl.admit("bulk", request_class="batch") as t:
        assert t.request_class == "batch"


# -- fleet: batch drains first ------------------------------------------------

def test_autoscaler_batch_drain_hook_fires_before_provider_drain(lm,
                                                                 tmp_path):
    from tpulab.fleet import FleetAutoscaler, ReplicaProvider

    events = []

    class FakeSet:
        def __init__(self):
            self.addresses = ["a", "b"]
            self.overloads = 0
            self.active = 2

        @property
        def active_count(self):
            return self.active

        @property
        def inflight(self):
            return [0, 0]

        def active_addresses(self):
            return list(self.addresses)

        def load_hints(self):
            return {a: 0 for a in self.addresses}

        def set_draining(self, addr, flag=True):
            events.append(("draining", addr))

        def retire_replica(self, addr):
            self.active -= 1

    class FakeProvider(ReplicaProvider):
        def drain(self, addr, timeout_s=30.0):
            events.append(("provider_drain", addr))
            return True

        def retire(self, addr):
            pass

    cb = _batcher(lm, lanes=1)
    try:
        held = TokenGate(2)
        sched = BatchScheduler(cb, sink=_gated_sink(held, tmp_path))
        _release_on_cancel(cb, held)
        th, res = _run_in_thread(
            sched, BatchJob("d", _prompts(1, rng_seed=6), steps=64))
        assert held.wait(timeout=60)  # mid-decode, and held

        def batch_drain(addr):
            events.append(("batch_drain", addr))
            sched.drain(addr)

        asc = FleetAutoscaler(FakeSet(), FakeProvider(),
                              wait_signal=lambda: 0.0, hold=1,
                              min_replicas=1, max_replicas=2,
                              batch_drain=batch_drain)
        assert asc.evaluate() == "drain_started"
        assert asc.wait_for_drain(10.0)
        # ordering: routing flip, then batch work yields, then the
        # provider drain (which only waits on online streams)
        kinds = [k for k, _ in events]
        assert kinds.index("batch_drain") < kinds.index("provider_drain")
        join_all([th], timeout_s=120)
        # the run ended without finishing (its in-flight was cancelled,
        # feeding paused) — delivered tokens stay durable for a resume
        assert res["items_done"] == 0 and sched.paused
        assert cb.active_lanes == 0     # the lane really freed
    finally:
        cb.shutdown()


# -- metrics ------------------------------------------------------------------

def test_batch_metrics_poll(lm, tmp_path):
    prometheus = pytest.importorskip("prometheus_client")
    from tpulab.utils.metrics import BatchMetrics
    cb = _batcher(lm)
    try:
        sink = JSONLResultSink(str(tmp_path / "m.jsonl"))
        sched = BatchScheduler(cb, sink=sink)
        m = BatchMetrics(registry=prometheus.CollectorRegistry())
        sched.run(BatchJob("m", _prompts(2, rng_seed=7), steps=4),
                  timeout_s=120)
        m.poll(sched)

        def val(name):
            return m.registry.get_sample_value(name)

        assert val("tpulab_batch_jobs_done_total") == 1
        assert val("tpulab_batch_items_done_total") == 2
        assert val("tpulab_batch_tokens_delivered_total") == 8
        assert val("tpulab_batch_jobs_running") == 0
        assert val("tpulab_batch_soak_utilization") == 0.0
    finally:
        cb.shutdown()


# -- RPC: request_class end to end -------------------------------------------

def test_rpc_generate_request_class_end_to_end(lm):
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          GenerationRejected,
                                          RemoteInferenceManager)
    from tpulab.serving import AdmissionConfig, AdmissionController
    cb = _batcher(lm)
    adm = AdmissionController(AdmissionConfig(max_inflight=4), load=cb)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb}, admission=adm)
    try:
        remote = RemoteInferenceManager(
            f"localhost:{mgr.server.bound_port}")
        gc = GenerateStreamClient(remote, "lm")
        prompt = _prompts(1, rng_seed=8)[0]
        want = cb.submit(prompt, 5).result(timeout=120)
        got = list(gc.generate(prompt, 5, request_class="batch"))
        assert got == want              # the class never changes tokens
        assert adm.batch_admitted_total == 1
        with pytest.raises(GenerationRejected):  # unknown class rejected
            list(gc.generate(prompt, 5, request_class="bulk"))
        with pytest.raises(GenerationRejected):  # class x disagg rejected
            list(gc.generate(prompt, 5, request_class="batch",
                             prefill_only=True))
    finally:
        mgr.shutdown()
        cb.shutdown()
