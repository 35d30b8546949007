"""Prometheus metrics (reference examples/02 metrics.h/cc:27-107 — singleton
Exposer+Registry; compute/request duration summaries with p50/p90/p99
quantiles; load-ratio histogram {1.25,1.5,2,10,100}; device power gauge
polled from Server::Run's control lambda).

prometheus_client has no quantile Summary, so the duration summaries are
implemented the way the reference's consumers read them: sliding-window
reservoirs exported as per-quantile gauges, next to total count/sum counters.
The NVML power gauge's TPU analog is the HBM usage gauge (polled from the
server control lambda via :meth:`InferenceMetrics.poll_device`).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import numpy as np

try:
    from prometheus_client import (CollectorRegistry, Counter, Gauge,
                                   Histogram, start_http_server)
    HAVE_PROMETHEUS = True
except ImportError:  # pragma: no cover
    HAVE_PROMETHEUS = False

#: reference load-ratio buckets (metrics.cc): request_time / compute_time
LOAD_RATIO_BUCKETS = (1.25, 1.5, 2.0, 10.0, 100.0)

_QUANTILES = (0.5, 0.9, 0.99)

#: latency-distribution buckets (seconds).  TTFT/queue cover the serving
#: SLO range (1 ms .. 10 s); ITL is finer (decode ticks are sub-10ms on
#: chip); e2e stretches to streaming-request lifetimes.
TTFT_BUCKETS = (.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1., 2.5,
                5., 10.)
ITL_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5, 1.)
E2E_BUCKETS = (.01, .025, .05, .1, .25, .5, 1., 2.5, 5., 10., 30., 60.)
#: deadline slack-at-completion: how close completed requests run to their
#: budget (small slack = the deadline is doing work; see OBSERVABILITY.md)
SLACK_BUCKETS = (.001, .005, .01, .025, .05, .1, .25, .5, 1., 2.5, 5.,
                 10., 30.)

#: circuit-breaker states exported per replica (rpc/replica.py)
BREAKER_STATES = ("closed", "open", "probing")


class _Reservoir:
    """Sliding-window quantile reservoir backing a 'summary'."""

    def __init__(self, size: int = 2048):
        self._buf = np.zeros(size, np.float64)
        self._n = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            self._buf[self._n % len(self._buf)] = value
            self._n += 1

    def quantile(self, q: float) -> float:
        with self._lock:
            n = min(self._n, len(self._buf))
            if n == 0:
                return 0.0
            return float(np.percentile(self._buf[:n], q * 100))


class InferenceMetrics:
    """The example-02 metric set for one service."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self._request = _Reservoir()
        self._compute = _Reservoir()
        self.request_count = Counter(
            f"{ns}_request_total", "Requests completed", registry=self.registry)
        # Gauges (not Counters) so the exported sample keeps the summary
        # convention `..._seconds_sum` — Counter would append `_total`.
        self.request_seconds_sum = Gauge(
            f"{ns}_request_duration_seconds_sum", "Total request seconds",
            registry=self.registry)
        self.compute_seconds_sum = Gauge(
            f"{ns}_compute_duration_seconds_sum", "Total compute seconds",
            registry=self.registry)
        self.request_quantiles = Gauge(
            f"{ns}_request_duration_seconds", "Request duration quantiles",
            ["quantile"], registry=self.registry)
        self.compute_quantiles = Gauge(
            f"{ns}_compute_duration_seconds", "Compute duration quantiles",
            ["quantile"], registry=self.registry)
        self.load_ratio = Histogram(
            f"{ns}_load_ratio", "request/compute duration ratio",
            buckets=LOAD_RATIO_BUCKETS, registry=self.registry)
        self.hbm_bytes_in_use = Gauge(
            f"{ns}_hbm_bytes_in_use", "Device HBM in use (power-gauge analog)",
            registry=self.registry)
        self.framework_hbm_bytes = Gauge(
            f"{ns}_framework_hbm_bytes",
            "HBM owned via the device allocator framework (weights, KV "
            "page stores) — the size_tracker figure",
            registry=self.registry)
        self.queue_depth = Gauge(
            f"{ns}_queue_depth", "In-flight requests (NVRPC_METRICS hook)",
            registry=self.registry)
        # -- per-model dimension (multi-model serving) ----------------------
        self.model_requests = Counter(
            f"{ns}_requests_by_model", "Requests completed, per model",
            ["model"], registry=self.registry)
        self.model_request_seconds = Histogram(
            f"{ns}_request_duration_seconds_by_model",
            "Request latency distribution, per model",
            ["model"], buckets=E2E_BUCKETS, registry=self.registry)
        # quantile refresh cadence state: counter + lock live here (not
        # lazily in observe_request) so two racing observers cannot both
        # read a stale count and both skip the refresh
        self._since_refresh = 0
        self._ever_refreshed = False
        self._refresh_lock = threading.Lock()

    # -- observation hooks ---------------------------------------------------
    _REFRESH_EVERY = 64  # quantile refresh cadence (full reservoir sort)

    def observe_request(self, request_s: float, compute_s: float,
                        model: Optional[str] = None) -> None:
        self.request_count.inc()
        if model:
            self.model_requests.labels(model=model).inc()
            self.model_request_seconds.labels(model=model).observe(
                max(0.0, request_s))
        self.request_seconds_sum.inc(request_s)
        self.compute_seconds_sum.inc(compute_s)
        self._request.observe(request_s)
        self._compute.observe(compute_s)
        if compute_s > 0:
            self.load_ratio.observe(request_s / compute_s)
        # quantile gauges refresh periodically (and from the control lambda),
        # not per request — the sort is too heavy for the hot path.  The
        # count-and-decide is atomic under the lock, so exactly one of N
        # racing observers crosses the threshold and pays the sort (the
        # pre-fix getattr dance let two skip it — or double-sort).  The
        # very first observation refreshes immediately (scrape freshness);
        # after that the cadence is every ``_REFRESH_EVERY``.
        with self._refresh_lock:
            self._since_refresh += 1
            do_refresh = (not self._ever_refreshed
                          or self._since_refresh >= self._REFRESH_EVERY)
        if do_refresh:
            self.refresh_quantiles()

    def refresh_quantiles(self) -> None:
        with self._refresh_lock:
            self._since_refresh = 0
            self._ever_refreshed = True
        for q in _QUANTILES:
            self.request_quantiles.labels(quantile=str(q)).set(
                self._request.quantile(q))
            self.compute_quantiles.labels(quantile=str(q)).set(
                self._compute.quantile(q))

    def inc_queue_depth(self) -> None:
        self.queue_depth.inc()

    def dec_queue_depth(self) -> None:
        self.queue_depth.dec()

    def poll_device(self, device_index: int = 0) -> None:
        """Control-lambda hook (reference NVML power gauge in Server::Run)."""
        from tpulab.tpu.device_info import DeviceInfo
        info = DeviceInfo.memory_info(device_index)
        if info.bytes_in_use is not None:
            self.hbm_bytes_in_use.set(info.bytes_in_use)
        from tpulab.tpu.allocators import TpuRawAllocator
        self.framework_hbm_bytes.set(TpuRawAllocator.total_bytes_in_use())
        self.refresh_quantiles()  # scrape-freshness without hot-path sorts


class ReplicaSetMetrics:
    """Observability for client-side replica routing
    (:mod:`tpulab.rpc.replica`): per-replica traffic/inflight/liveness,
    the failover counter, circuit-breaker state/transitions, per-attempt
    status-code counters and end-to-end deadline outcomes — the
    client-side view envoy's upstream stats give in deployment, plus the
    resilience telemetry the adaptive-orchestration line in PAPERS.md
    argues breakers/deadlines need in order to be tunable."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.requests = Counter(
            f"{ns}_replica_requests_total",
            "Requests completed per replica", ["replica"],
            registry=self.registry)
        self.failovers = Counter(
            f"{ns}_replica_failovers_total",
            "Requests re-routed off a failed replica",
            registry=self.registry)
        self.inflight = Gauge(
            f"{ns}_replica_inflight", "In-flight requests per replica",
            ["replica"], registry=self.registry)
        self.live = Gauge(
            f"{ns}_replica_live",
            "Last health-probe liveness per replica (1/0)", ["replica"],
            registry=self.registry)
        # -- circuit breaker (one-hot state + transition counters) ----------
        self.breaker_state = Gauge(
            f"{ns}_replica_breaker_state",
            "Circuit-breaker state per replica (one-hot over "
            "closed/open/probing)", ["replica", "state"],
            registry=self.registry)
        self.breaker_transitions = Counter(
            f"{ns}_replica_breaker_transitions_total",
            "Breaker transitions per replica, keyed by target state",
            ["replica", "to"], registry=self.registry)
        # -- per-attempt outcomes (retry/failover tuning input) -------------
        self.attempts = Counter(
            f"{ns}_replica_attempts_total",
            "Request attempts by terminal status code (OK, UNAVAILABLE, "
            "DEADLINE_EXCEEDED, INVALID_ARGUMENT, ...)", ["code"],
            registry=self.registry)
        # -- end-to-end deadline outcomes -----------------------------------
        self.deadline_outcomes = Counter(
            f"{ns}_deadline_outcomes_total",
            "Deadline-bounded requests by outcome (met/exceeded)",
            ["outcome"], registry=self.registry)
        self.deadline_slack = Histogram(
            f"{ns}_deadline_slack_seconds",
            "Remaining budget at completion of deadline-bounded requests",
            buckets=SLACK_BUCKETS, registry=self.registry)
        # -- durable streams (docs/ROBUSTNESS.md "Stream failover
        # semantics"): how much failover work was wasted vs resumed ------
        self.stalls = Counter(
            f"{ns}_replica_stream_stalls_total",
            "Streams failed over by the stall watchdog (no first token "
            "within the TTFT bound / no progress within the inter-token "
            "bound) — distinct from transport faults", registry=self.registry)
        self.resumes = Counter(
            f"{ns}_replica_stream_resumes_total",
            "Failover attempts resubmitted as resume-from-delivered "
            "(prompt+delivered re-prefilled, zero tokens replayed)",
            registry=self.registry)
        self.resume_fallbacks = Counter(
            f"{ns}_replica_stream_resume_fallbacks_total",
            "Resume attempts the server rejected, degraded to full replay",
            registry=self.registry)
        self.tokens_replayed = Counter(
            f"{ns}_replica_tokens_replayed_total",
            "Already-delivered tokens re-received and discarded on "
            "full-replay failovers (the waste resume removes)",
            registry=self.registry)
        self.hedges = Counter(
            f"{ns}_replica_hedges_total",
            "Duplicate first-token attempts launched after the hedge delay",
            registry=self.registry)
        self.hedge_wins = Counter(
            f"{ns}_replica_hedge_wins_total",
            "Hedged requests whose duplicate attempt delivered the first "
            "token (the primary lost the race)", registry=self.registry)
        # -- per-replica prefix-cache effectiveness (poll_load refreshes
        # from StatusResponse.prefix_hits/prefix_lookups) — the fleet view
        # prefix-affinity routing (ROADMAP item 1) needs: a returning
        # user landing on a random replica shows up here as hit rates
        # collapsing as the fleet widens ------------------------------------
        self.prefix_hits = Gauge(
            f"{ns}_replica_prefix_hits",
            "Server-reported prefix-cache pages served from cache, "
            "per replica (lifetime counter sampled as a gauge)",
            ["replica"], registry=self.registry)
        self.prefix_lookups = Gauge(
            f"{ns}_replica_prefix_lookups",
            "Server-reported prefix-cache pages looked up (hits + "
            "misses), per replica", ["replica"], registry=self.registry)
        # -- prefix-affinity routing (tpulab.fleet.router): did requests
        # land on their rendezvous home, and how much cache warmth do
        # membership changes cost ----------------------------------------
        self.affinity_hits = Counter(
            f"{ns}_replica_affinity_hits_total",
            "Requests routed to their prefix-affinity winner (rank 0 of "
            "the rendezvous ring)", registry=self.registry)
        self.affinity_spills = Counter(
            f"{ns}_replica_affinity_spills_total",
            "Requests whose affinity winner was skipped for load (queue "
            "depth / inflight / free-HBM spill thresholds) — the "
            "hot-prefix-never-a-hot-spot contract, counted",
            registry=self.registry)
        self.ring_moves = Counter(
            f"{ns}_replica_ring_moves_total",
            "Sampled prefix digests re-homed by ring membership changes "
            "(breaker ejections, drains, scale up/down) — rendezvous "
            "hashing keeps this near sampled/N per change",
            registry=self.registry)

    # -- hooks (called by the replica sets; cold paths) ---------------------
    def set_breaker_state(self, replica: str, state: str) -> None:
        """One-hot the per-replica state gauge (PromQL reads
        ``..._breaker_state{state="open"} == 1``)."""
        for s in BREAKER_STATES:
            self.breaker_state.labels(replica=replica, state=s).set(
                1 if s == state else 0)

    def note_breaker_transition(self, replica: str, to_state: str) -> None:
        self.breaker_transitions.labels(replica=replica, to=to_state).inc()
        self.set_breaker_state(replica, to_state)

    def note_attempt(self, code: str) -> None:
        self.attempts.labels(code=code).inc()

    def observe_deadline(self, met: bool,
                         slack_s: Optional[float] = None) -> None:
        self.deadline_outcomes.labels(
            outcome="met" if met else "exceeded").inc()
        if met and slack_s is not None:
            self.deadline_slack.observe(max(0.0, slack_s))

    # -- durable-stream hooks -------------------------------------------
    def note_stall(self) -> None:
        self.stalls.inc()

    def note_resume(self) -> None:
        self.resumes.inc()

    def note_resume_fallback(self) -> None:
        self.resume_fallbacks.inc()

    def note_tokens_replayed(self, n: int = 1) -> None:
        if n > 0:
            self.tokens_replayed.inc(n)

    def note_hedge(self, won: bool = False) -> None:
        if won:
            self.hedge_wins.inc()
        else:
            self.hedges.inc()

    # -- prefix-affinity hooks (tpulab.fleet.router) --------------------
    def note_affinity(self, hit: bool) -> None:
        if hit:
            self.affinity_hits.inc()
        else:
            self.affinity_spills.inc()

    def note_ring_moves(self, n: int = 1) -> None:
        if n > 0:
            self.ring_moves.inc(n)


class FleetMetrics:
    """Observability for the fleet control plane
    (:mod:`tpulab.fleet`): autoscaler membership actions and the
    queue-wait signal it scales on, plus the self-healing/election
    telemetry (:mod:`tpulab.fleet.supervisor` /
    :mod:`tpulab.fleet.election`) — replica deaths and respawns, the
    crash-loop breaker alert, and which node currently leads.  The
    elasticity telemetry the adaptive-orchestration line in PAPERS.md
    argues a scale controller needs in order to be tunable (is it
    flapping? is the wait threshold doing work? is a slot burning spawn
    budget?)."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.scale_ups = Counter(
            f"{ns}_fleet_scale_ups_total",
            "Replicas added by the autoscaler", registry=self.registry)
        self.scale_downs = Counter(
            f"{ns}_fleet_scale_downs_total",
            "Replicas retired by the autoscaler (drain completed)",
            registry=self.registry)
        self.drains = Counter(
            f"{ns}_fleet_drains_total",
            "Scale-down drains started (victim flagged draining; retired "
            "only once in-flight work completes)", registry=self.registry)
        self.replicas = Gauge(
            f"{ns}_fleet_replicas",
            "Active (routable, non-draining) replicas in the set",
            registry=self.registry)
        self.queue_wait = Gauge(
            f"{ns}_fleet_queue_wait_ewma_seconds",
            "The admission queue-wait EWMA the controller last evaluated "
            "(AdmissionController.queue_wait_ewma_s)",
            registry=self.registry)
        self.replica_deaths = Counter(
            f"{ns}_fleet_replica_deaths_total",
            "Replicas the supervisor declared dead (process exited or "
            "unreachable past the probe streak) — drains never count",
            registry=self.registry)
        self.respawns = Counter(
            f"{ns}_fleet_respawns_total",
            "Crashed replicas respawned by the supervisor (after "
            "exponential backoff)", registry=self.registry)
        self.crash_loops = Counter(
            f"{ns}_fleet_crash_loops_total",
            "Crash-loop breaker openings: a lineage died N times in the "
            "window and is quarantined (spawn budget stops burning; "
            "THIS is the alert to page on)", registry=self.registry)
        self.leader_transitions = Counter(
            f"{ns}_fleet_leader_transitions_total",
            "Times THIS node gained control-plane leadership (lease "
            "acquisitions; fleet-wide churn = sum over nodes)",
            registry=self.registry)
        self.is_leader = Gauge(
            f"{ns}_fleet_is_leader",
            "1 while this node holds the control-plane lease (runs the "
            "supervisor + autoscaler), else 0", registry=self.registry)
        self._was_leader = False

    # -- hooks (called by the control plane; cold paths) ----------------
    def note_scale(self, up: bool) -> None:
        (self.scale_ups if up else self.scale_downs).inc()

    def note_drain(self) -> None:
        self.drains.inc()

    def set_replicas(self, n: int) -> None:
        self.replicas.set(n)

    def set_queue_wait(self, seconds: float) -> None:
        self.queue_wait.set(max(0.0, float(seconds)))

    def note_death(self) -> None:
        self.replica_deaths.inc()

    def note_respawn(self) -> None:
        self.respawns.inc()

    def note_crash_loop(self) -> None:
        self.crash_loops.inc()

    def set_leader(self, leading: bool) -> None:
        """Gauge + edge-triggered transition counter (gains only)."""
        leading = bool(leading)
        self.is_leader.set(1 if leading else 0)
        if leading and not self._was_leader:
            self.leader_transitions.inc()
        self._was_leader = leading


class BatchMetrics:
    """Offline-batch-lane telemetry (`_batch_*`; tpulab.batch,
    docs/SERVING.md "Offline batch lane"): job/item progress, how often
    online traffic evicted the lane, tokens delivered vs re-decode the
    checkpoint resume avoided, and the utilization-soak gauge — is the
    lane actually converting idle capacity into tokens.  Sampled by
    ``poll(scheduler)`` (cheap attribute reads; counters advance by the
    delta since the last poll, so rate() works in PromQL)."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.jobs_running = Gauge(
            f"{ns}_batch_jobs_running",
            "Batch jobs a scheduler is currently running",
            registry=self.registry)
        self.jobs_done = Counter(
            f"{ns}_batch_jobs_done_total",
            "Batch jobs run to completion (every item done)",
            registry=self.registry)
        self.jobs_interrupted = Counter(
            f"{ns}_batch_jobs_interrupted_total",
            "Batch runs killed mid-job (chaos/timeout); the next run "
            "resumes from the JSONL checkpoint", registry=self.registry)
        self.items_done = Counter(
            f"{ns}_batch_items_done_total",
            "Job items (prompts) completed", registry=self.registry)
        self.preemptions = Counter(
            f"{ns}_batch_preemptions_total",
            "Batch-class lanes evicted by online arrivals (the lane is "
            "the FIRST preemption victim by design — a high count with "
            "healthy online latencies is the lane working)",
            registry=self.registry)
        self.tokens_delivered = Counter(
            f"{ns}_batch_tokens_delivered_total",
            "Tokens delivered to batch result sinks",
            registry=self.registry)
        self.tokens_replay_avoided = Counter(
            f"{ns}_batch_tokens_replay_avoided_total",
            "Delivered tokens a checkpoint resume did NOT re-decode "
            "(the prompt+delivered prefix rode one chunked prefill)",
            registry=self.registry)
        self.spare_denials = Counter(
            f"{ns}_batch_spare_denials_total",
            "Feed attempts deferred by the spare-capacity gate (idle "
            "lanes / unified headroom / arbiter floor)",
            registry=self.registry)
        self.soak_utilization = Gauge(
            f"{ns}_batch_soak_utilization",
            "Fraction of engine lanes the batch lane occupies right now "
            "(near 1 on an idle fleet, near 0 under online load — both "
            "are the lane working as designed)", registry=self.registry)
        self._last: Dict[str, int] = {}

    def _advance(self, counter, key: str, value: int) -> None:
        delta = value - self._last.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last[key] = value

    def poll(self, scheduler) -> None:
        """Sample a tpulab.batch.BatchScheduler (control-loop hook)."""
        self.jobs_running.set(getattr(scheduler, "jobs_running", 0))
        self.soak_utilization.set(
            getattr(scheduler, "soak_utilization", 0.0))
        self._advance(self.jobs_done, "jobs_done",
                      getattr(scheduler, "jobs_done", 0))
        self._advance(self.jobs_interrupted, "interrupted",
                      getattr(scheduler, "interrupted_runs", 0))
        self._advance(self.items_done, "items_done",
                      getattr(scheduler, "items_done", 0))
        self._advance(self.tokens_delivered, "tokens",
                      getattr(scheduler, "tokens_delivered", 0))
        self._advance(self.tokens_replay_avoided, "replay_avoided",
                      getattr(scheduler, "tokens_resume_skipped", 0))
        self._advance(self.spare_denials, "spare_denials",
                      getattr(scheduler, "spare_denials", 0))
        eng = getattr(scheduler, "engine", None)
        if eng is not None:
            self._advance(self.preemptions, "preemptions",
                          getattr(eng, "batch_preemptions", 0))


class GenerationMetrics:
    """LLM-serving observability for a ContinuousBatcher: lane/queue/page
    gauges plus token/request/preemption/prefix-cache counters.  Sampled
    by ``poll(batcher)`` (cheap attribute reads; counters advance by the
    delta since the last poll, so rate() works in PromQL).

    Latency DISTRIBUTIONS (TTFT, inter-token latency, queue wait, end to
    end) are event-driven, not polled: pass this object as the batcher's
    ``metrics=`` and it observes every completed request at the source —
    the distinction the inference-frameworks-benchmark line in PAPERS.md
    shows actually separates serving stacks (means hide the tail).
    ``ttft_quantiles()`` / ``itl_quantiles()`` read sliding-window
    reservoirs (exact quantiles, not bucket interpolation)."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None,
                 model: str = ""):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        #: model name tagging this engine's per-model samples (multi-model
        #: serving: one GenerationMetrics per engine; "" = untagged)
        self.model_label = model
        self.active_lanes = Gauge(
            f"{ns}_llm_active_lanes", "Decode lanes in use",
            registry=self.registry)
        self.queued = Gauge(
            f"{ns}_llm_queued_requests", "Requests waiting for a lane",
            registry=self.registry)
        self.free_pages = Gauge(
            f"{ns}_llm_free_pages", "KV pool pages free",
            registry=self.registry)
        self.tokens = Counter(
            f"{ns}_llm_tokens", "Tokens generated",
            registry=self.registry)
        self.completed = Counter(
            f"{ns}_llm_requests_completed", "Generation requests completed",
            registry=self.registry)
        self.preemptions = Counter(
            f"{ns}_llm_preemptions", "Priority preemptions",
            registry=self.registry)
        self.prefix_hits = Counter(
            f"{ns}_llm_prefix_cache_hits", "Prefix-cache page hits",
            registry=self.registry)
        self.prefix_misses = Counter(
            f"{ns}_llm_prefix_cache_misses", "Prefix pages computed fresh",
            registry=self.registry)
        # -- latency distributions (observed per request by the batcher) ----
        self.ttft = Histogram(
            f"{ns}_llm_ttft_seconds",
            "Time to first token (submit -> first emitted token)",
            buckets=TTFT_BUCKETS, registry=self.registry)
        self.itl = Histogram(
            f"{ns}_llm_inter_token_seconds",
            "Inter-token latency (per decoded token after the first)",
            buckets=ITL_BUCKETS, registry=self.registry)
        self.queue_wait = Histogram(
            f"{ns}_llm_queue_wait_seconds",
            "Submit -> prefill start (lane + page admission wait)",
            buckets=TTFT_BUCKETS, registry=self.registry)
        self.e2e = Histogram(
            f"{ns}_llm_e2e_seconds",
            "Submit -> last token (completed requests)",
            buckets=E2E_BUCKETS, registry=self.registry)
        self.deadline_expired = Counter(
            f"{ns}_llm_deadline_expired_total",
            "Requests the batcher cancelled at deadline expiry",
            registry=self.registry)
        # -- fused-decode dispatch efficiency (multi-step decode blocks) ----
        self.decode_dispatches = Counter(
            f"{ns}_llm_decode_dispatches",
            "Fused decode dispatches (K-token blocks and single ticks)",
            registry=self.registry)
        self.decode_host_syncs = Counter(
            f"{ns}_llm_decode_host_syncs",
            "Blocking device->host result fetches in decode",
            registry=self.registry)
        self.ragged_dispatches = Counter(
            f"{ns}_llm_ragged_dispatches",
            "Dispatches through the ragged paged-attention family "
            "(mixed prefill+decode rounds, plus decode/verify dispatches "
            "whose attention ran the pallas ragged kernel)",
            registry=self.registry)
        self.dispatches_by_kind = Counter(
            f"{ns}_llm_dispatches_by_kind",
            "Decode dispatches by ragged-plan dispatch kind "
            "(decode = K-blocks/single ticks, verify = speculative "
            "draft+verify blocks, mixed = ragged prefill+decode rounds)",
            ["kind"], registry=self.registry)
        self.tokens_per_dispatch = Gauge(
            f"{ns}_llm_tokens_per_dispatch",
            "Generated tokens per decode dispatch (lifetime ratio; ~K x "
            "lanes when fused blocks run full)", registry=self.registry)
        self.host_syncs_per_token = Gauge(
            f"{ns}_llm_host_syncs_per_token",
            "Blocking host syncs per generated token (1.0 = per-token "
            "round trips; ~1/(K*lanes) under fused decode)",
            registry=self.registry)
        # -- speculative decode (draft/verify blocks; engine/paged.py) ------
        self.spec_tokens_drafted = Counter(
            f"{ns}_llm_spec_tokens_drafted",
            "Draft-model proposals verified by the target (accepted or "
            "rejected)", registry=self.registry)
        self.spec_tokens_accepted = Counter(
            f"{ns}_llm_spec_tokens_accepted",
            "Draft proposals the target accepted (emitted as output "
            "tokens)", registry=self.registry)
        self.spec_fallbacks = Counter(
            f"{ns}_llm_spec_fallbacks",
            "Lanes degraded from speculative to plain decode blocks "
            "(low acceptance, chaos verify trips)",
            registry=self.registry)
        self.spec_probes = Counter(
            f"{ns}_llm_spec_probes",
            "Probe blocks re-trying speculation on a transiently degraded "
            "lane (acceptance-EWMA degrades only)", registry=self.registry)
        self.spec_probe_recoveries = Counter(
            f"{ns}_llm_spec_probe_recoveries",
            "Probe blocks whose lane recovered to speculative decode "
            "(acceptance back above the floor)", registry=self.registry)
        self.spec_acceptance_rate = Gauge(
            f"{ns}_llm_spec_acceptance_rate",
            "Lifetime draft acceptance rate (accepted / drafted) — the "
            "multiplier on the decode-block dispatch amortization",
            registry=self.registry)
        # -- durable streams: server-side resume admissions -----------------
        self.resumed_streams = Counter(
            f"{ns}_llm_resumed_streams",
            "Generate streams admitted as resume-from-delivered "
            "(prompt+delivered through one chunked prefill)",
            registry=self.registry)
        self.tokens_resume_skipped = Counter(
            f"{ns}_llm_tokens_resume_skipped",
            "Already-delivered tokens a resume admission did NOT re-decode "
            "(each rode the prefill instead of a sequential decode step)",
            registry=self.registry)
        # -- per-model dimension (multi-model serving) ----------------------
        self.model_tokens = Counter(
            f"{ns}_llm_tokens_by_model", "Tokens generated, per model",
            ["model"], registry=self.registry)
        self.model_completed = Counter(
            f"{ns}_llm_requests_completed_by_model",
            "Generation requests completed, per model",
            ["model"], registry=self.registry)
        self.model_ttft = Histogram(
            f"{ns}_llm_ttft_seconds_by_model",
            "Time to first token, per model",
            ["model"], buckets=TTFT_BUCKETS, registry=self.registry)
        self.model_itl = Histogram(
            f"{ns}_llm_inter_token_seconds_by_model",
            "Inter-token latency, per model",
            ["model"], buckets=ITL_BUCKETS, registry=self.registry)
        self._ttft_res = _Reservoir()
        self._itl_res = _Reservoir()
        self._last: Dict[str, int] = {}

    # -- event hooks (called by the batcher; see engine/paged.py) -----------
    def observe_queue_wait(self, seconds: float) -> None:
        self.queue_wait.observe(max(0.0, seconds))

    def observe_ttft(self, seconds: float) -> None:
        seconds = max(0.0, seconds)
        self.ttft.observe(seconds)
        if self.model_label:
            self.model_ttft.labels(model=self.model_label).observe(seconds)
        self._ttft_res.observe(seconds)

    def observe_itl(self, seconds: float) -> None:
        seconds = max(0.0, seconds)
        self.itl.observe(seconds)
        if self.model_label:
            self.model_itl.labels(model=self.model_label).observe(seconds)
        self._itl_res.observe(seconds)

    def observe_e2e(self, seconds: float) -> None:
        self.e2e.observe(max(0.0, seconds))

    def note_deadline_expired(self) -> None:
        self.deadline_expired.inc()

    def note_resume(self, tokens_skipped: int) -> None:
        """One resume-from-delivered admission (Generate RPC): the
        delivered prefix rode the prefill instead of re-decoding."""
        self.resumed_streams.inc()
        if tokens_skipped > 0:
            self.tokens_resume_skipped.inc(tokens_skipped)

    def ttft_quantiles(self) -> Dict[str, float]:
        return {f"p{int(q * 100)}": self._ttft_res.quantile(q)
                for q in _QUANTILES}

    def itl_quantiles(self) -> Dict[str, float]:
        return {f"p{int(q * 100)}": self._itl_res.quantile(q)
                for q in _QUANTILES}

    def _advance(self, counter, key: str, value: int) -> None:
        delta = value - self._last.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last[key] = value

    def poll(self, batcher) -> None:
        """Sample a ContinuousBatcher (control-loop / poller hook)."""
        self.active_lanes.set(batcher.active_lanes)
        self.queued.set(batcher.queued_requests)
        try:
            self.free_pages.set(batcher.pool.free_pages)
        except AttributeError:  # closed/absent pool during teardown (a
            pass                # wrapped engine without .pool, or a pool
            #                     whose accounting died with close()) — any
            #                     other failure is a real bug and raises
        self._advance(self.tokens, "tokens", batcher.tokens_generated)
        self._advance(self.completed, "completed",
                      batcher.completed_requests)
        if self.model_label:
            self._advance(self.model_tokens.labels(model=self.model_label),
                          "model_tokens", batcher.tokens_generated)
            self._advance(
                self.model_completed.labels(model=self.model_label),
                "model_completed", batcher.completed_requests)
        self._advance(self.preemptions, "preempt", batcher.preemptions)
        # fused-decode dispatch efficiency (getattr: wrapped engines may
        # not expose the counters)
        dispatches = getattr(batcher, "decode_dispatches", 0)
        syncs = getattr(batcher, "decode_host_syncs", 0)
        self._advance(self.decode_dispatches, "dispatches", dispatches)
        self._advance(self.decode_host_syncs, "syncs", syncs)
        self._advance(self.ragged_dispatches, "ragged",
                      getattr(batcher, "ragged_dispatches", 0))
        for kind, n in getattr(batcher, "dispatch_kinds", {}).items():
            self._advance(self.dispatches_by_kind.labels(kind=kind),
                          f"kind_{kind}", n)
        # speculative decode telemetry: tokens_generated counts EMITTED
        # (accepted) tokens only, so tokens_per_dispatch below is never
        # inflated by drafted-but-rejected proposals — those show up
        # exclusively in the drafted/accepted pair and the rate gauge
        drafted = getattr(batcher, "spec_tokens_drafted", 0)
        accepted = getattr(batcher, "spec_tokens_accepted", 0)
        self._advance(self.spec_tokens_drafted, "spec_drafted", drafted)
        self._advance(self.spec_tokens_accepted, "spec_accepted", accepted)
        self._advance(self.spec_fallbacks, "spec_fallbacks",
                      getattr(batcher, "spec_fallbacks", 0))
        self._advance(self.spec_probes, "spec_probes",
                      getattr(batcher, "spec_probes", 0))
        self._advance(self.spec_probe_recoveries, "spec_probe_recoveries",
                      getattr(batcher, "spec_probe_recoveries", 0))
        if drafted:
            self.spec_acceptance_rate.set(accepted / drafted)
        if dispatches:
            self.tokens_per_dispatch.set(
                batcher.tokens_generated / dispatches)
        if batcher.tokens_generated:
            self.host_syncs_per_token.set(
                syncs / batcher.tokens_generated)
        pc = getattr(batcher, "prefix_cache", None)
        if pc is not None:
            self._advance(self.prefix_hits, "hits", pc.hits)
            self._advance(self.prefix_misses, "misses", pc.misses)


#: swap latency buckets (seconds): device<->host page copies
SWAP_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
                1., 2.5)


class KVTierMetrics:
    """Tiered-KV-cache telemetry (`_kv_tier_*`; tpulab.kvcache): swap
    in/out bytes and latency distributions, demotion/promotion/drop
    counters, recompute-tokens-saved, and host-tier occupancy gauges —
    the view that says whether HBM pressure is being absorbed by the
    host tier (demotions + promotions + tokens saved) or still destroying
    state (drops + swap failures).  Latency/bytes are event-driven (pass
    this object as the manager's ``metrics=``); counters/gauges advance
    via :meth:`poll`."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.swap_out_bytes = Counter(
            f"{ns}_kv_tier_swap_out_bytes",
            "KV bytes copied device->host (lane swaps + demotions)",
            registry=self.registry)
        self.swap_in_bytes = Counter(
            f"{ns}_kv_tier_swap_in_bytes",
            "KV bytes copied host->device (restores + promotions)",
            registry=self.registry)
        self.swap_out_seconds = Histogram(
            f"{ns}_kv_tier_swap_out_seconds",
            "Swap-out latency (gather dispatch -> host-tier resident; "
            "write-behind, so this is BEHIND the decode loop)",
            buckets=SWAP_BUCKETS, registry=self.registry)
        self.swap_in_seconds = Histogram(
            f"{ns}_kv_tier_swap_in_seconds",
            "Swap-in latency (restore entry -> scatter dispatched)",
            buckets=SWAP_BUCKETS, registry=self.registry)
        self.swap_outs = Counter(
            f"{ns}_kv_tier_swap_outs", "Preempted-lane KV snapshots taken",
            registry=self.registry)
        self.swap_ins = Counter(
            f"{ns}_kv_tier_swap_ins",
            "Recompute-free resumes (snapshot restored, no re-prefill)",
            registry=self.registry)
        self.demotions = Counter(
            f"{ns}_kv_tier_demotions",
            "Prefix-cache pages demoted to the host tier",
            registry=self.registry)
        self.promotions = Counter(
            f"{ns}_kv_tier_promotions",
            "Prefix-cache pages promoted back from the host tier",
            registry=self.registry)
        self.swap_failures = Counter(
            f"{ns}_kv_tier_swap_failures",
            "Swaps degraded to the recompute path (chaos, transfer "
            "errors)", registry=self.registry)
        self.swap_drops = Counter(
            f"{ns}_kv_tier_swap_drops",
            "Snapshots the host tier's budget refused (distinct from "
            "transfer failures: a sustained count means the host budget "
            "is undersized)", registry=self.registry)
        self.host_drops = Counter(
            f"{ns}_kv_tier_host_drops",
            "Payloads refused by the host tier (larger than the budget)",
            registry=self.registry)
        self.host_evictions = Counter(
            f"{ns}_kv_tier_host_evictions",
            "Host-tier LRU entries pushed out by budget pressure",
            registry=self.registry)
        self.recompute_tokens_saved = Counter(
            f"{ns}_kv_tier_recompute_tokens_saved",
            "Prefill tokens resumes did NOT recompute (the tier's work "
            "saved, in tokens)", registry=self.registry)
        self.host_bytes = Gauge(
            f"{ns}_kv_tier_host_bytes", "Host-tier payload bytes resident",
            registry=self.registry)
        self.host_entries = Gauge(
            f"{ns}_kv_tier_host_entries", "Host-tier entries resident",
            registry=self.registry)
        self._last: Dict[str, int] = {}

    # -- event hooks (called by KVOffloadManager) ----------------------------
    def observe_swap_out(self, seconds: float, nbytes: int) -> None:
        self.swap_out_seconds.observe(max(0.0, seconds))

    def observe_swap_in(self, seconds: float, nbytes: int) -> None:
        self.swap_in_seconds.observe(max(0.0, seconds))

    def _advance(self, counter, key: str, value: int) -> None:
        delta = value - self._last.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last[key] = value

    def poll(self, manager) -> None:
        """Sample a KVOffloadManager (control-loop / poller hook)."""
        self._advance(self.swap_out_bytes, "ob", manager.swap_out_bytes)
        self._advance(self.swap_in_bytes, "ib", manager.swap_in_bytes)
        self._advance(self.swap_outs, "so", manager.swap_outs)
        self._advance(self.swap_ins, "si", manager.swap_ins)
        self._advance(self.demotions, "dem", manager.demotions)
        self._advance(self.promotions, "pro", manager.promotions)
        self._advance(self.swap_failures, "fail", manager.swap_failures)
        self._advance(self.swap_drops, "sdrop", manager.swap_drops)
        self._advance(self.recompute_tokens_saved, "saved",
                      manager.recompute_tokens_saved)
        store = manager.store
        self._advance(self.host_drops, "drops", store.drops)
        self._advance(self.host_evictions, "evict", store.evictions)
        self.host_bytes.set(store.bytes_used)
        self.host_entries.set(len(store))


class KVFabricMetrics:
    """Fleet KV fabric telemetry (`_kvfabric_*`; tpulab.kvfabric): pull
    counts/bytes and fetch-latency distribution, single-flight
    coalesces, cost-gate skips, degrades and recompute-tokens-saved —
    the view that says whether routed-astray requests are adopting the
    fleet's warmth (pulls + tokens saved) or still recomputing it
    (degrades), and whether the guard rails are earning their keep
    (coalesces under fetch storms, cost-gate skips when the wire is
    slower than the chip).  Latency/bytes are event-driven (pass this
    object as the fabric's ``metrics=``); counters advance via
    :meth:`poll`."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.pulls = Counter(
            f"{ns}_kvfabric_pulls",
            "Prefix-KV pulls fetched from a home replica and adopted "
            "locally (each replaced a whole local prefill)",
            registry=self.registry)
        self.pull_bytes = Counter(
            f"{ns}_kvfabric_pull_bytes",
            "Wire bytes fetched over FetchKV", registry=self.registry)
        self.pull_seconds = Histogram(
            f"{ns}_kvfabric_pull_seconds",
            "FetchKV fetch latency (RPC start -> snapshot decoded and "
            "geometry-validated)", buckets=SWAP_BUCKETS,
            registry=self.registry)
        self.coalesced = Counter(
            f"{ns}_kvfabric_coalesced",
            "Concurrent same-digest misses served by another thread's "
            "in-flight fetch (single-flight)", registry=self.registry)
        self.cost_gate_skips = Counter(
            f"{ns}_kvfabric_cost_gate_skips",
            "Pulls skipped because the fetch-time estimate exceeded the "
            "local recompute estimate", registry=self.registry)
        self.degrades = Counter(
            f"{ns}_kvfabric_degrades",
            "Pull attempts degraded to a local prefill (NOT_FOUND, "
            "chaos, transport, corrupt wire, budget refusal, admission "
            "rejection)", registry=self.registry)
        self.recompute_tokens_saved = Counter(
            f"{ns}_kvfabric_recompute_tokens_saved",
            "Prefill tokens pulls did NOT recompute (the fabric's work "
            "saved, in tokens)", registry=self.registry)
        self._last: Dict[str, int] = {}

    # -- event hooks (called by KVFabric) ------------------------------------
    def observe_pull(self, seconds: float, nbytes: int) -> None:
        self.pull_seconds.observe(max(0.0, seconds))

    def _advance(self, counter, key: str, value: int) -> None:
        delta = value - self._last.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last[key] = value

    def poll(self, fabric) -> None:
        """Sample a KVFabric (control-loop / poller hook)."""
        self._advance(self.pulls, "p", fabric.pulls)
        self._advance(self.pull_bytes, "pb", fabric.pull_bytes)
        self._advance(self.coalesced, "co", fabric.coalesced)
        self._advance(self.cost_gate_skips, "cg", fabric.cost_gate_skips)
        self._advance(self.degrades, "dg", fabric.degrades)
        self._advance(self.recompute_tokens_saved, "sv",
                      fabric.recompute_tokens_saved)


class ModelStoreMetrics:
    """Multi-model weight-tier telemetry (`_modelstore_*`;
    tpulab.modelstore): resident-vs-host-tier model gauges, weight swap
    in/out counters + latency distributions, evictions and cold rebuilds
    — the view that says whether the hot set is cycling cheaply
    (swap-ins, bounded latency) or thrashing back to cold rebuilds
    (failures + rebuilds).  Latency/bytes are event-driven (pass this
    object as the multiplexer's ``metrics=``); counters/gauges advance
    via :meth:`poll`."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.resident_models = Gauge(
            f"{ns}_modelstore_resident_models",
            "Models currently HBM-resident (hot)", registry=self.registry)
        self.host_tier_models = Gauge(
            f"{ns}_modelstore_host_tier_models",
            "Models parked in the host weight tier (cold)",
            registry=self.registry)
        self.hbm_bytes = Gauge(
            f"{ns}_modelstore_hbm_bytes",
            "Weight bytes accounted against the HBM budget (hot models "
            "plus unsettled swaps)", registry=self.registry)
        self.host_bytes = Gauge(
            f"{ns}_modelstore_host_bytes",
            "Host-tier weight bytes resident", registry=self.registry)
        self.swap_ins = Counter(
            f"{ns}_modelstore_swap_ins",
            "Models promoted host->device (bit-exact weight restores)",
            registry=self.registry)
        self.swap_outs = Counter(
            f"{ns}_modelstore_swap_outs",
            "Model weight snapshots landed device->host (write-behind)",
            registry=self.registry)
        self.swap_in_bytes = Counter(
            f"{ns}_modelstore_swap_in_bytes",
            "Weight bytes copied host->device", registry=self.registry)
        self.swap_out_bytes = Counter(
            f"{ns}_modelstore_swap_out_bytes",
            "Weight bytes copied device->host", registry=self.registry)
        self.swap_in_seconds = Histogram(
            f"{ns}_modelstore_swap_in_seconds",
            "Swap-in latency (host pop -> weights attached)",
            buckets=SWAP_BUCKETS, registry=self.registry)
        self.swap_out_seconds = Histogram(
            f"{ns}_modelstore_swap_out_seconds",
            "Swap-out latency (detach -> host-tier resident; write-"
            "behind, so this is BEHIND the request path)",
            buckets=SWAP_BUCKETS, registry=self.registry)
        self.evictions = Counter(
            f"{ns}_modelstore_evictions",
            "Models pushed out of HBM by budget pressure",
            registry=self.registry)
        self.cold_rebuilds = Counter(
            f"{ns}_modelstore_cold_rebuilds",
            "Acquires served by a fresh build (weights in no tier: "
            "degraded swaps, host-budget refusals)",
            registry=self.registry)
        self.swap_failures = Counter(
            f"{ns}_modelstore_swap_failures",
            "Weight swaps degraded to the cold-rebuild path (chaos, "
            "transfer errors)", registry=self.registry)
        self.swap_drops = Counter(
            f"{ns}_modelstore_swap_drops",
            "Weight snapshots the host tier's budget refused (sustained "
            "count = host budget undersized)", registry=self.registry)
        self.host_evictions = Counter(
            f"{ns}_modelstore_host_evictions",
            "Host-tier LRU models pushed out by budget pressure",
            registry=self.registry)
        self._last: Dict[str, int] = {}

    # -- event hooks (called by WeightMultiplexer) ---------------------------
    def observe_swap_in(self, seconds: float, nbytes: int) -> None:
        self.swap_in_seconds.observe(max(0.0, seconds))

    def observe_swap_out(self, seconds: float, nbytes: int) -> None:
        self.swap_out_seconds.observe(max(0.0, seconds))

    def _advance(self, counter, key: str, value: int) -> None:
        delta = value - self._last.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last[key] = value

    def poll(self, mux) -> None:
        """Sample a WeightMultiplexer (control-loop / poller hook)."""
        self._advance(self.swap_ins, "si", mux.swap_ins)
        self._advance(self.swap_outs, "so", mux.swap_outs)
        self._advance(self.swap_in_bytes, "sib", mux.swap_in_bytes)
        self._advance(self.swap_out_bytes, "sob", mux.swap_out_bytes)
        self._advance(self.evictions, "ev", mux.evictions)
        self._advance(self.cold_rebuilds, "cr", mux.cold_rebuilds)
        self._advance(self.swap_failures, "sf", mux.swap_failures)
        self._advance(self.swap_drops, "sd", mux.swap_drops)
        self._advance(self.host_evictions, "he", mux.store.evictions)
        self.resident_models.set(len(mux.resident_models()))
        self.host_tier_models.set(len(mux.host_models()))
        self.hbm_bytes.set(mux.hbm_bytes_in_use)
        self.host_bytes.set(mux.store.bytes_used)


class HBMMetrics:
    """Unified-HBM-economy telemetry (`_hbm_*`; tpulab.hbm): per-tenant
    occupancy and claim-count gauges, the single headroom gauge, and the
    pressure-protocol counters (pressure rounds, forced KV demotions,
    forced model evictions, denials) — the view that says whether the
    device-memory economy is trading bytes productively (demotions +
    evictions, headroom near zero) or thrashing/denying (denials
    climbing, pressure rounds without reclaims).  Counters/gauges
    advance via :meth:`poll` over an
    :class:`~tpulab.hbm.HBMArbiter`."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.capacity_bytes = Gauge(
            f"{ns}_hbm_capacity_bytes",
            "Device-HBM budget the arbiter trades within",
            registry=self.registry)
        self.headroom_bytes = Gauge(
            f"{ns}_hbm_headroom_bytes",
            "THE headroom number: capacity minus every tenant's ledger "
            "claims (negative = over-committed discovery)",
            registry=self.registry)
        self.tenant_bytes = Gauge(
            f"{ns}_hbm_tenant_bytes",
            "Ledger bytes claimed per tenant (weights / kv / scratch)",
            ["tenant"], registry=self.registry)
        self.tenant_claims = Gauge(
            f"{ns}_hbm_tenant_claims",
            "Live ledger claims per tenant (models resident, pools, "
            "measured jits)", ["tenant"], registry=self.registry)
        self.pressure_events = Counter(
            f"{ns}_hbm_pressure_events",
            "Pressure rounds run (a request found no free headroom)",
            registry=self.registry)
        self.demotions = Counter(
            f"{ns}_hbm_demotions",
            "Pressure rounds where the KV tenant reclaimed (idle KV "
            "demoted to the host tier, pool shrunk)",
            registry=self.registry)
        self.evictions = Counter(
            f"{ns}_hbm_evictions",
            "Pressure rounds where the weights tenant reclaimed (cold "
            "unleased models swapped out)", registry=self.registry)
        self.denials = Counter(
            f"{ns}_hbm_denials",
            "Requests denied (timeout or nothing reclaimable) — the "
            "requester degraded to its static-budget behavior",
            registry=self.registry)
        self.grants = Counter(
            f"{ns}_hbm_grants", "Requests granted ledger bytes",
            registry=self.registry)
        self._last: Dict[str, int] = {}

    def _advance(self, counter, key: str, value: int) -> None:
        delta = value - self._last.get(key, 0)
        if delta > 0:
            counter.inc(delta)
        self._last[key] = value

    def poll(self, arbiter) -> None:
        """Sample an HBMArbiter (control-loop / poller hook)."""
        self.capacity_bytes.set(arbiter.capacity_bytes)
        self.headroom_bytes.set(arbiter.free_hbm_bytes)
        led = arbiter.ledger
        for tenant in led.tenants():
            self.tenant_bytes.labels(tenant=tenant).set(
                led.tenant_bytes(tenant))
            self.tenant_claims.labels(tenant=tenant).set(
                led.tenant_claims(tenant))
        self._advance(self.pressure_events, "pe", arbiter.pressure_events)
        self._advance(self.demotions, "dem", arbiter.demotions_forced)
        self._advance(self.evictions, "ev", arbiter.evictions_forced)
        self._advance(self.denials, "den", arbiter.denials)
        self._advance(self.grants, "gr", arbiter.grants)


class AdmissionMetrics:
    """Admission-control telemetry (`_admission_*`; serving/admission.py):
    admitted/rejected/shed counters keyed by tenant (and rejection
    reason), queue-wait-at-admission distribution, and live queue/inflight
    pressure gauges — the overload view docs/SERVING.md reads: *is the
    frontend shedding, whom, and why*."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.admitted = Counter(
            f"{ns}_admission_admitted_total", "Requests admitted",
            ["tenant"], registry=self.registry)
        self.rejected = Counter(
            f"{ns}_admission_rejected_total",
            "Requests rejected at admission, by reason (global_rate, "
            "tenant_rate, queue_full, shed, deadline, queue_timeout, "
            "chaos)", ["reason", "tenant"], registry=self.registry)
        self.shed = Counter(
            f"{ns}_admission_shed_total",
            "Queued requests shed for a higher-priority arrival",
            ["tenant"], registry=self.registry)
        self.queue_wait = Histogram(
            f"{ns}_admission_queue_wait_seconds",
            "Fair-queue wait of ADMITTED requests (arrival -> dispatch)",
            buckets=TTFT_BUCKETS, registry=self.registry)
        self.queue_depth = Gauge(
            f"{ns}_admission_queue_depth",
            "Requests waiting in the admission fair queue",
            registry=self.registry)
        self.inflight = Gauge(
            f"{ns}_admission_inflight",
            "Admitted requests currently holding a ticket",
            registry=self.registry)
        self._queue_wait_res = _Reservoir()

    # -- hooks (called by AdmissionController) ------------------------------
    def note_admitted(self, tenant: str, queue_wait_s: float) -> None:
        self.admitted.labels(tenant=tenant).inc()
        self.queue_wait.observe(max(0.0, queue_wait_s))
        self._queue_wait_res.observe(max(0.0, queue_wait_s))

    def note_rejected(self, reason: str, tenant: str) -> None:
        self.rejected.labels(reason=reason, tenant=tenant).inc()
        if reason == "shed":
            self.shed.labels(tenant=tenant).inc()

    def set_pressure(self, queued: int, inflight: int) -> None:
        self.queue_depth.set(queued)
        self.inflight.set(inflight)

    def queue_wait_quantiles(self) -> Dict[str, float]:
        """Exact sliding-window quantiles."""
        return {f"p{int(q * 100)}": self._queue_wait_res.quantile(q)
                for q in _QUANTILES}


class ChaosMetrics:
    """Fault-injection telemetry: one counter per (trip point, action), fed
    by the :func:`tpulab.chaos.set_observer` hook — a chaos experiment is
    then self-measuring (the injected-fault count sits on the same /metrics
    endpoint as the breaker/deadline reactions it provoked)."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        self.injections = Counter(
            f"{namespace}_chaos_injections_total",
            "Chaos rules fired, keyed by trip point and action",
            ["point", "action"], registry=self.registry)

    def observe(self, point: str, action: str) -> None:
        self.injections.labels(point=point, action=action).inc()

    def install(self) -> "ChaosMetrics":
        """Register as the process-wide chaos fire observer."""
        from tpulab import chaos
        chaos.set_observer(self.observe)
        return self

    def uninstall(self) -> None:
        from tpulab import chaos
        chaos.set_observer(None)


class SLOMetrics:
    """Per-tenant SLO telemetry (`_slo_*`; tpulab.obs.slo,
    docs/OBSERVABILITY.md "Fleet observability"): raw request/error/
    latency-breach counters per (tenant, request class) plus the
    multi-window burn-rate gauges — the "is tenant X meeting its SLO"
    scrape surface, and the alerting input the classic fast+slow
    multi-window burn alerts read."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.requests = Counter(
            f"{ns}_slo_requests_total",
            "SLO-accounted requests per tenant and request class "
            "(client-cancelled requests are excluded — neither good "
            "nor bad)", ["tenant", "request_class"],
            registry=self.registry)
        self.errors = Counter(
            f"{ns}_slo_errors_total",
            "Requests that failed the availability objective (terminal "
            "outcome not SUCCESS), per tenant and request class",
            ["tenant", "request_class"], registry=self.registry)
        self.latency_breaches = Counter(
            f"{ns}_slo_latency_breaches_total",
            "Requests whose end-to-end latency exceeded the objective, "
            "per tenant and request class",
            ["tenant", "request_class"], registry=self.registry)
        self.availability_burn = Gauge(
            f"{ns}_slo_availability_burn_rate",
            "Availability error-budget burn rate per tenant/class/"
            "window (1.0 = budget exhausted exactly over the objective "
            "period; >1 = burning early)",
            ["tenant", "request_class", "window"],
            registry=self.registry)
        self.latency_burn = Gauge(
            f"{ns}_slo_latency_burn_rate",
            "Latency error-budget burn rate per tenant/class/window",
            ["tenant", "request_class", "window"],
            registry=self.registry)

    # -- hooks (tpulab.obs.slo.SLOTracker) ------------------------------
    def note_request(self, tenant: str, request_class: str,
                     error: bool, breach: bool) -> None:
        self.requests.labels(tenant=tenant,
                             request_class=request_class).inc()
        if error:
            self.errors.labels(tenant=tenant,
                               request_class=request_class).inc()
        if breach:
            self.latency_breaches.labels(
                tenant=tenant, request_class=request_class).inc()

    def set_burn(self, tenant: str, request_class: str, window: str,
                 availability: float, latency: float) -> None:
        self.availability_burn.labels(
            tenant=tenant, request_class=request_class,
            window=window).set(float(availability))
        self.latency_burn.labels(
            tenant=tenant, request_class=request_class,
            window=window).set(float(latency))


class FederationMetrics:
    """Federated fleet view (`_fed_*`; tpulab.fleet.observer): the
    FleetObserver refreshes these replica-labeled gauges from each
    fleetz scrape's Status RPCs, so ONE /metrics endpoint on the
    observer node shows every replica's load/headroom/drain state
    side by side — the poor-operator's Prometheus federation.  Children
    for replicas that leave the snapshot are pruned on the next scrape
    (the stale-label-child discipline retire_replica follows)."""

    def __init__(self, namespace: str = "tpulab",
                 registry: Optional["CollectorRegistry"] = None):
        if not HAVE_PROMETHEUS:  # pragma: no cover
            raise RuntimeError("prometheus_client unavailable")
        self.registry = registry or CollectorRegistry()
        ns = namespace
        self.scrapes = Counter(
            f"{ns}_fed_scrapes_total",
            "Federated fleet snapshots assembled by the observer",
            registry=self.registry)
        self.scrape_seconds = Gauge(
            f"{ns}_fed_scrape_seconds",
            "Wall-clock cost of the last federated snapshot (all "
            "replica Status RPCs + assembly)", registry=self.registry)
        self.replicas = Gauge(
            f"{ns}_fed_replicas",
            "Replicas in the last federated snapshot",
            registry=self.registry)
        self.up = Gauge(
            f"{ns}_fed_replica_up",
            "1 when the replica answered its Status RPC in the last "
            "snapshot, else 0", ["replica"], registry=self.registry)
        self.inflight = Gauge(
            f"{ns}_fed_replica_inflight",
            "Server-reported in-flight requests per replica",
            ["replica"], registry=self.registry)
        self.queued = Gauge(
            f"{ns}_fed_replica_queued",
            "Server-reported queued requests per replica",
            ["replica"], registry=self.registry)
        self.free_hbm_bytes = Gauge(
            f"{ns}_fed_replica_free_hbm_bytes",
            "Server-reported free HBM headroom per replica",
            ["replica"], registry=self.registry)
        self.free_kv_pages = Gauge(
            f"{ns}_fed_replica_free_kv_pages",
            "Server-reported free KV-cache pages per replica",
            ["replica"], registry=self.registry)
        self.draining = Gauge(
            f"{ns}_fed_replica_draining",
            "1 while the replica reports itself draining, else 0",
            ["replica"], registry=self.registry)
        self.prefix_hits = Gauge(
            f"{ns}_fed_replica_prefix_hits",
            "Server-reported lifetime prefix-cache hits per replica",
            ["replica"], registry=self.registry)
        self.prefix_lookups = Gauge(
            f"{ns}_fed_replica_prefix_lookups",
            "Server-reported lifetime prefix-cache lookups per replica",
            ["replica"], registry=self.registry)
        self.resident_models = Gauge(
            f"{ns}_fed_replica_resident_models",
            "Models resident in device memory per replica",
            ["replica"], registry=self.registry)
        self._seen: set = set()
        self._per_replica = (self.up, self.inflight, self.queued,
                             self.free_hbm_bytes, self.free_kv_pages,
                             self.draining, self.prefix_hits,
                             self.prefix_lookups, self.resident_models)

    # -- hooks (tpulab.fleet.observer.FleetObserver) --------------------
    def observe_scrape(self, seconds: float, replicas: int) -> None:
        self.scrapes.inc()
        self.scrape_seconds.set(float(seconds))
        self.replicas.set(int(replicas))

    def set_replica(self, replica: str, up: bool, inflight: int = 0,
                    queued: int = 0, free_hbm_bytes: int = 0,
                    free_kv_pages: int = 0, draining: bool = False,
                    prefix_hits: int = 0, prefix_lookups: int = 0,
                    resident_models: int = 0) -> None:
        self._seen.add(replica)
        self.up.labels(replica=replica).set(1 if up else 0)
        self.inflight.labels(replica=replica).set(int(inflight))
        self.queued.labels(replica=replica).set(int(queued))
        self.free_hbm_bytes.labels(replica=replica).set(
            int(free_hbm_bytes))
        self.free_kv_pages.labels(replica=replica).set(
            int(free_kv_pages))
        self.draining.labels(replica=replica).set(1 if draining else 0)
        self.prefix_hits.labels(replica=replica).set(int(prefix_hits))
        self.prefix_lookups.labels(replica=replica).set(
            int(prefix_lookups))
        self.resident_models.labels(replica=replica).set(
            int(resident_models))

    def prune(self, keep) -> None:
        """Drop label children for replicas no longer in the snapshot —
        a retired replica must stop exporting, not freeze at its last
        value."""
        for replica in self._seen - set(keep):
            for g in self._per_replica:
                try:
                    g.remove(replica)
                except KeyError:  # pragma: no cover - never created
                    pass
        self._seen &= set(keep)


class MultiRegistryCollector:
    """Aggregating collector: exposes several CollectorRegistry instances
    through one registry (hence one /metrics port).  Metric names must be
    disjoint across the sub-registries — true by construction for the
    collectors in this module (``_request_*`` / ``_replica_*`` / ``_llm_*``
    / ``_admission_*`` / ``_kv_tier_*`` / ``_chaos_*`` prefixes)."""

    def __init__(self, registries: Sequence["CollectorRegistry"]):
        self._registries = list(registries)

    def collect(self):
        for reg in self._registries:
            yield from reg.collect()


def start_metrics_server(metrics, port: int = 9090):
    """Expose /metrics (reference Exposer on :8080).

    ``metrics`` is a metrics holder with a ``registry`` attribute
    (InferenceMetrics, ReplicaSetMetrics, GenerationMetrics, ChaosMetrics,
    ...), a bare CollectorRegistry, or a list/tuple of either — multiple
    holders are aggregated behind ONE port via
    :class:`MultiRegistryCollector` (a serving process exports its
    request, routing, generation and chaos telemetry from a single
    scrape target)."""
    if isinstance(metrics, (list, tuple)):
        agg = CollectorRegistry()
        agg.register(MultiRegistryCollector(
            [getattr(m, "registry", m) for m in metrics]))
        return start_http_server(port, registry=agg)
    return start_http_server(port, registry=getattr(metrics, "registry",
                                                    metrics))
