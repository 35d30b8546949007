"""Continuous batching over the paged KV cache: the scheduler.

The dense :mod:`generation` engine leases one max_len cache per session; this
module is the scalable successor (the TPU literature's ragged/paged-attention
serving shape): K/V live in a global pool of fixed-size *pages*
(:mod:`tpulab.engine.kv_pool`), sessions own *block tables* of page ids, and
:class:`ContinuousBatcher` steps every active session in one fused batched
decode per tick — continuous batching: new requests join the batch the moment
a slot frees, finished ones leave without draining the rest.  The programs it
jits and dispatches (decode blocks, mixed rounds, the speculative block and
its draft's warm-up) are the pure functions of
:mod:`tpulab.engine.paged_steps`; this file holds the request
(:class:`SamplingParams`, ``_PagedRequest``), the process-level jit memo and
the scheduler, and nothing else.
"""

from __future__ import annotations

import threading
import time as _time
from collections import OrderedDict
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

import numpy as np

from tpulab import chaos
from tpulab.core.deadline import Deadline, DeadlineExceeded
from tpulab.core.threads import on_one_frame_chunk
from tpulab.engine.kv_pool import LaneStateStore, PagedKVPool, PrefixCache
from tpulab.engine.paged_steps import (ROUND_STOPS, StepPrograms, moe_shape,
                                       pack_round, pack_words, result_fields,
                                       unpack_words)
from tpulab.engine.plan import plan_engine
from tpulab.utils import tracing
from tpulab.utils.tracing import part, stage


class SamplingParams:
    """Token selection policy (greedy by default).

    ``device=False`` (default): host-side temperature / top-k sampling
    with a per-request numpy PRNG — requires fetching the lane's full
    (vocab,) logits row every tick.

    ``device=True``: TPU-first temperature sampling computed ON CHIP
    (Gumbel-max over the logits with a per-lane key folded from
    (seed, position)) — the tick fetches only (B,) token ids, never the
    logits.  Reproducible per request (the key depends only on seed and
    position, not batch-mates or preemption) but a DIFFERENT stream than
    the host PRNG.  ``top_k`` / ``top_p`` are host-side features:
    device=True with either set is rejected (per-lane truncation is not
    a static compile-time shape).

    ``top_p`` (nucleus sampling, 0 < top_p < 1) keeps the smallest set
    of tokens whose probabilities sum to at least top_p; composes with
    ``top_k`` (k-truncation first, then the nucleus), the standard order.
    """

    __slots__ = ("temperature", "top_k", "top_p", "device", "seed", "_rng")

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 seed: Optional[int] = None, device: bool = False,
                 top_p: float = 0.0):
        if temperature < 0:
            raise ValueError("temperature must be >= 0")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError("top_p must be in [0, 1]")
        if device and (top_k > 0 or 0.0 < top_p < 1.0):
            raise ValueError("device sampling does not support top_k/top_p "
                             "(per-lane truncation is not a static shape); "
                             "use host sampling")
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.device = device
        if seed is None:
            # full 64-bit draw: device sampling keys on both seed words,
            # a 31-bit default would zero the hi word for every unseeded
            # request and shrink the stream space
            seed = int(np.random.default_rng().integers(
                0, 2**64, dtype=np.uint64))
        self.seed = int(seed)
        self._rng = np.random.default_rng(seed)

    def pick(self, logits: np.ndarray) -> int:
        """Select the next token from a (vocab,) logits row."""
        if self.temperature == 0.0:
            return int(logits.argmax())
        z = logits.astype(np.float64) / self.temperature
        if self.top_k > 0 and self.top_k < z.shape[0]:
            kth = np.partition(z, -self.top_k)[-self.top_k]
            z = np.where(z >= kth, z, -np.inf)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        if 0.0 < self.top_p < 1.0:
            # nucleus: smallest prob-descending prefix summing >= top_p
            order = np.argsort(p)[::-1]
            csum = np.cumsum(p[order])
            cut = int(np.searchsorted(csum, self.top_p)) + 1
            mask = np.zeros_like(p, dtype=bool)
            mask[order[:cut]] = True
            p = np.where(mask, p, 0.0)
            p /= p.sum()
        return int(self._rng.choice(z.shape[0], p=p))


class _PagedRequest:
    __slots__ = ("prompt", "steps", "future", "tokens_out", "pages",
                 "length", "pending_prompt", "on_token", "cancelled",
                 "sampling", "priority", "resumed", "admit_seq",
                 "stop_tokens", "want_logprobs", "logprobs_out", "deadline",
                 "trace_id", "t_submit", "t_prefill0", "t_first", "t_last",
                 "chunk_t0", "chunk_start", "kv_handle", "export_digest",
                 "draft_pages", "draft_len", "spec_enabled", "spec_ewma",
                 "spec_drafted", "spec_accepted", "spec_probe_in",
                 "spec_probing", "tenant", "lane", "fl", "batch",
                 "pf_started", "pf_digests", "pf_shared", "pf_t0",
                 "eva_done", "walk_runs", "wpages", "wfirst")

    def __init__(self, prompt: np.ndarray, steps: int, on_token=None,
                 sampling: Optional[SamplingParams] = None,
                 priority: int = 0, stop_tokens=None,
                 logprobs: bool = False, deadline: Optional[float] = None,
                 trace_id: Optional[str] = None,
                 tenant: Optional[str] = None, batch: bool = False):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.steps = steps
        self.future: Future = Future()
        self.tokens_out: List[int] = []
        self.pages: List[int] = []
        self.length = 0
        self.pending_prompt = list(self.prompt)
        self.on_token = on_token
        self.cancelled = False
        self.sampling = sampling or SamplingParams()
        self.priority = priority
        #: offline batch lane (docs/SERVING.md "Offline batch lane"):
        #: batch requests rank strictly below EVERY online request —
        #: they queue behind all online arrivals regardless of priority
        #: and are the first preemption victims when an online arrival
        #: needs a lane or pages.  Within the batch class, priority and
        #: FIFO order apply as usual.
        self.batch = bool(batch)
        self.resumed = False     # preempted mid-decode; resume skips the
        #                          prefill pick (its token was already emitted)
        self.kv_handle = None    # host-tier KV snapshot of a preempted lane
        #                          (kvcache.SwapHandle); resume swaps it back
        #                          in instead of re-prefilling
        self.export_digest = None  # disagg: demote finished KV to the host
        #                            tier under ("ship", digest) at release
        self.admit_seq = -1      # admission order (preemption tie-break)
        self.stop_tokens = frozenset(int(t) for t in (stop_tokens or ()))
        self.want_logprobs = logprobs
        self.logprobs_out: List[float] = []
        #: absolute monotonic expiry (None = unbounded); the scheduler's
        #: per-iteration sweep cancels expired requests before their next
        #: step, freeing the lane and pages
        self.deadline = deadline
        # -- speculative decode lane state (second page table) --------------
        self.draft_pages: List[int] = []  # draft KV page ids (never shared)
        self.draft_len = 0         # context positions the draft KV covers
        self.spec_enabled = True   # False: plain blocks (chaos verify trip
        #                            degrades for the REST of the request;
        #                            an acceptance-EWMA degrade is transient
        #                            — see spec_probe_in)
        self.spec_probe_in = None  # plain dispatches until the next probe
        #                            block re-tries speculation (None = no
        #                            probe scheduled: never degraded, or
        #                            degraded permanently by chaos)
        self.spec_probing = False  # the next/current spec dispatch is a
        #                            probe: its acceptance decides recovery
        self.spec_ewma = 1.0       # rolling acceptance (optimistic start)
        self.spec_drafted = 0      # draft proposals verified for this lane
        self.spec_accepted = 0     # of those, emitted (accepted) ones
        # -- request-lifecycle telemetry (trace spans + latency metrics) ----
        self.trace_id = trace_id
        #: admission tenant (flight-recorder / debugz attribution only —
        #: the scheduler never reads it)
        self.tenant = tenant
        #: last lane this request occupied (-1 = never admitted)
        self.lane = -1
        #: flight-recorder per-request detail (None = recorder disarmed:
        #: the scheduling hot path pays one None check per site)
        self.fl: Optional[dict] = None
        # -- a prompt on its way in, a chunk a round ------------------------
        self.pf_started = False      # pages secured, chunks may dispatch
        self.pf_digests = None       # full-prompt-page digests (insert at
        #                              prompt completion)
        self.pf_shared = 0           # prefix-cache pages served shared
        self.pf_t0: Optional[float] = None  # this prefill's start (spans)
        #: EVA windows of this lane already compacted into summary rows:
        #: its rows are ``length - eva_done * (window - summaries)``
        self.eva_done = 0
        #: :meth:`run_blocks`: key blocks of the page table looked at, and
        #: those among them that were one ascending run of ids
        self.walk_runs = (0, 0)
        #: the lane's pages in the page store's WINDOW group (a model with
        #: window layers): whole key blocks of the walk, entries ``[wfirst,
        #: wfirst + len(wpages))`` of its window table; the blocks behind
        #: the window have gone back to the group
        self.wpages: List[int] = []
        self.wfirst = 0
        self.t_submit = _time.perf_counter()
        self.t_prefill0: Optional[float] = None  # first prefill start
        self.t_first: Optional[float] = None     # first emitted token
        self.t_last: Optional[float] = None      # latest emitted token
        self.chunk_t0: Optional[float] = None    # open decode-chunk start
        self.chunk_start = 0                     # first token idx in chunk

    def run_blocks(self, g_pages: int, full: int) -> int:
        """Of the page table's first ``full`` blocks of ``g_pages`` entries,
        those whose ids are one ascending run (what the page walk fetches
        with one copy).  Kept as the table grows: a block is looked at
        once, O(1) a page (a table cut below what was looked at is counted
        again from its start)."""
        seen, runs = self.walk_runs
        if full < seen:
            seen = runs = 0
        while seen < full:
            blk = self.pages[seen * g_pages:(seen + 1) * g_pages]
            runs += blk == list(range(blk[0], blk[0] + g_pages))
            seen += 1
        self.walk_runs = (seen, runs)
        return runs

    def finished(self) -> bool:
        """steps exhausted, or the last emitted token is a stop token
        (which stays in the output, ending it)."""
        return bool(self.tokens_out) and (
            len(self.tokens_out) >= self.steps
            or self.tokens_out[-1] in self.stop_tokens)


class ContinuousBatcher:
    """Continuous-batching scheduler over the paged pool.

    ``submit(prompt, steps) -> Future[list[int]]``; a background scheduler
    thread runs one fused decode dispatch per iteration over up to
    ``lanes`` concurrent requests, admitting queued requests whenever a
    lane (and pages) free up — no head-of-line draining.
    ``cancel(future)`` aborts a request and frees its lane/pages at the
    next dispatch boundary.

    Multi-step fused decode: each dispatch covers an adaptive K decode
    ticks (``decode_block`` is the ceiling) chained on device via
    :func:`paged_decode_block`, so the host pays ONE dispatch + ONE
    blocking fetch per K tokens instead of per token — off-chip the
    per-token cost is the link RTT, and K amortizes it.  Greedy and
    device-sampled lanes run at full K (sampling and the EOS /
    steps-remaining stop mask live on device); any host-sampled
    (``top_k``/``top_p``) lane in the batch drops the whole batch to K=1
    (its sampling needs the logits row on host every token).  K adapts
    down to 1-2 when a lane's deadline is tight or a streaming consumer
    is attached with no queue pressure, so interactive TTFT/ITL does not
    regress; per-token ``on_token`` callbacks still fire in order, and
    cancellation/deadline sweeps act at block boundaries (a request stops
    within at most one block of the sweep observing it).  A dispatch
    crosses the host-device boundary once each way: what the host knows
    goes in as one packed buffer (:meth:`_put`), the tokens, their
    log-probabilities and the expert counters come back as one
    (:meth:`_fetch`); ``debug_state()["dispatch"]["transfers"]`` counts
    both.

    Speculative decoding (``draft_params=``, docs/PERFORMANCE.md): a
    small draft model (e.g. :func:`tpulab.models.transformer.
    early_exit_draft`) rides the SAME paged pool through a second
    per-lane page table; each fused dispatch drafts K tokens, verifies
    them in one batched target forward, and emits up to K+1 accepted
    tokens — multiplying the block amortization by the acceptance rate
    with bit-identical output.  Host-sampled lanes never speculate, and
    lanes degrade to plain blocks on low acceptance, chaos verify trips,
    or draft-table pool pressure.

    Sharded serving (``mesh=``, tpulab.parallel): a ``{"model": M}`` mesh
    runs this replica tensor-parallel over M devices — params placed by
    the Megatron-TP partition rules, the KV page store sharded on the
    KV-heads dim (page tables stay replicated), every dispatch a sharded
    jit whose collectives ride INSIDE the fused program.  Emitted tokens
    are bit-identical to mesh=None for greedy and device-sampled
    streams, and the host-sync count per block is unchanged — see
    docs/PERFORMANCE.md "Sharded serving".

    The dispatch plan (docs/PERFORMANCE.md "Ragged paged attention";
    ``use_kernel`` chooses the attention under it, the Pallas kernels or
    the XLA gather, never the plan): prompts and decode
    lanes advance together through fused mixed rounds
    (:func:`paged_mixed_step`) — per-lane (query_len, kv_len) segments
    packed by token, at most ``RAGGED_CHUNK_CAP`` prompt tokens a round
    for all lanes together (512, or the widest power of two under it that
    ``max_len`` holds twice and the kernels' geometry rule admits at this
    engine's shapes: derived at construction,
    ``debug_state()["dispatch"]["round_budget"]``), ONE
    dispatch and one host sync per round, no separate prefill programs —
    and the speculative verify forward rides the same ragged kernel
    family.  The round is a member of the run-ahead chain: it takes the
    carry a decode block returns and returns one, so while a prompt waits
    every dispatch is a round that carries every decoding lane as a row
    from its predecessor's device carry, enqueued before that predecessor
    is fetched (:meth:`_chain_block`).

    Model spec (``spec=``, tpulab.models.spec): a ``ModelSpec`` names the
    attention kind, the layer kinds and the cache-entry kind; without one
    the engine serves the dense decoder of ``n_heads``/``n_kv_heads``.
    Multi-head latent attention keeps ONE latent row a token a layer in
    the page store and runs in the absorbed form (gather or the latent
    ragged kernel); expert layers run the routed FFN of
    tpulab.parallel.moe and count assignments (``debug_state()["moe"]``).
    Mamba and Gated DeltaNet layers (``spec.mixers``; a lane state of kind
    ``spec.state_kind``) leave no pages: their recurrent state
    lives in a :class:`~tpulab.engine.kv_pool.LaneStateStore`, a slot a
    lane, which rotates through every dispatch beside the page store (the
    run-ahead chain enqueues block N+1 on the state block N returns); the
    page store then holds the attention layers alone.  A CCA layer
    (``spec.cca_taps``) owns a layer of BOTH: K/V rows in the page store and
    its convolutions' tails in the lane's slot (``debug_state()["cca"]``).
    A segment that starts at position 0 starts from zeros on the device, so
    admission, lane reuse and a re-prefill after preemption need no reset
    dispatch (``debug_state()["state"]``).
    A learned indexer (``spec.index_topk``) keeps one index key a token a
    layer in ``pool.index``, under the page ids of K and V, and the pair
    ``(pool.kv, pool.index)`` rotates through every dispatch the same way;
    a query row attends to the ``index_topk`` keys the indexer selects
    (``tpulab.ops.sparse_attention``; ``debug_state()["sparse"]`` counts
    rows, keys scored and keys attended from the lengths committed).
    The options that such a spec's cache entry or per-lane state does not
    carry are refused at construction, by name.

    Tiered KV (``kv_offload=``, tpulab.kvcache): preemption swaps the
    victim's KV pages to a budgeted host-RAM tier (async, write-behind)
    and resume swaps them back with ZERO prefill dispatches; prefix-cache
    entries evicted under pool pressure demote to the host tier and
    promote back on the next lookup hit.  Every degraded swap falls back
    to the exact re-prefill/recompute path — see docs/PERFORMANCE.md.
    """

    #: explicit capability marker for routers (e.g. the Generate RPC)
    continuous_batching = True

    #: decode tokens per trace span ("each decode chunk"): per-token spans
    #: would swamp the bounded event ring at serving rates.  K>1 decode
    #: flushes one span per BLOCK instead (block-sized decode spans).
    TRACE_DECODE_CHUNK = 8

    #: fused-decode block sizes: the adaptive K snaps DOWN onto this menu
    #: so the jit cache stays tiny (one compiled scan per size in use)
    BLOCK_K_MENU = (1, 2, 4, 8, 16)

    #: shortest max_len at which use_kernel=None auto-selects the pallas
    #: kernel on TPU (see __init__'s auto-select comment)
    KERNEL_AUTO_MIN_CTX = 8192

    def __init__(self, params, n_heads: int, n_layers: int,
                 pool: Optional[PagedKVPool] = None, lanes: int = 4,
                 max_len: int = 256, page_size: int = 16,
                 n_pages: int = 0, compute_dtype=None, device=None,
                 use_kernel: Optional[bool] = None,
                 n_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 kv_dtype=None,
                 trace=None, metrics=None,
                 decode_block: int = 8,
                 kv_offload=None,
                 draft_params=None,
                 draft_n_layers: Optional[int] = None,
                 draft_n_heads: Optional[int] = None,
                 draft_n_kv_heads: Optional[int] = None,
                 spec_accept_floor: float = 0.35,
                 mesh=None, hbm=None, flight=None,
                 kv_publish: bool = False,
                 spec=None):
        import jax
        import jax.numpy as jnp
        from tpulab.models.transformer import weight_shape

        compute_dtype = compute_dtype or jnp.bfloat16
        vocab, d_model = weight_shape(params["embed"])[:2]
        #: what this engine is (tpulab.engine.plan): the model's kinds, the
        #: page store's geometry, ``use_kernel``, the round's budget;
        #: what it refuses it refuses here, before anything is allocated
        self.plan = plan = plan_engine(
            spec=spec, n_heads=n_heads, n_layers=n_layers,
            n_kv_heads=n_kv_heads, rope_theta=rope_theta,
            d_model=int(d_model), vocab=int(vocab), lanes=lanes,
            max_len=max_len, page_size=page_size,
            prefill_chunk=prefill_chunk, use_kernel=use_kernel,
            compute_dtype=compute_dtype, kv_dtype=kv_dtype,
            round_ceiling=self.RAGGED_CHUNK_CAP,
            kernel_auto_min_ctx=self.KERNEL_AUTO_MIN_CTX, pool=pool,
            mesh=mesh, hbm=hbm, draft_params=draft_params,
            draft_n_layers=draft_n_layers, draft_n_heads=draft_n_heads,
            draft_n_kv_heads=draft_n_kv_heads, kv_offload=kv_offload,
            kv_publish=kv_publish, prefix_cache=prefix_cache)
        if decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        if kv_publish and kv_offload in (None, False):
            raise ValueError("kv_publish requires kv_offload")
        #: tpulab.models.spec.ModelSpec (None: the dense decoder)
        self.model_spec = spec
        self.lanes = lanes
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages = plan.max_pages
        self.prefill_chunk = plan.prefill_chunk
        #: id-validation bound (public: the Generate RPC checks it too)
        self.vocab = plan.vocab
        self.use_kernel = plan.use_kernel
        # of a choice the engine no longer has (one dispatch plan, no flash
        # prefill), what the benchmark still prints (``perf/models/*.py``)
        # and subscripts (``sched.tokens_per_dispatch``: the key
        # ``prefill_dispatches`` of :meth:`debug_state`, a literal 0 there
        # beside ``"ragged": True``).  Nothing in ``tpulab/`` reads them; a
        # ``benchmark`` issue frees the names (ROADMAP D17)
        self.ragged, self.prefill_flash = True, False
        #: the widest budget THIS engine runs: what a harness sizes its
        #: warm-up prompts by (a power of two; the class's is the ceiling)
        self.RAGGED_CHUNK_CAP = plan.round_cap
        self.round_budget_why = plan.round_budget_why
        # +1: page 0 is the reserved scratch page.  GQA pools store the
        # compact n_kv_heads form — KV HBM shrinks by n_heads/n_kv_heads.
        self._owns_pool = pool is None
        self.pool = pool or PagedKVPool(
            n_pages or plan.max_pages * lanes + 1, page_size,
            plan.pool_layers, 0 if plan.latent else plan.n_kv,
            0 if plan.latent else plan.head_dim, plan.kv_dtype, device,
            mesh=mesh, latent_width=plan.latent, index_dim=plan.index_dim)
        #: the page store's WINDOW group (None: one group, ``pool``, as
        #: every model without window layers has): the window layers' own
        #: array, free extents, reference counts and a table a lane.  Sized
        #: here, from the lanes, the window and the rows a dispatch may
        #: write ahead (a round's budget; two decode blocks in flight), so
        #: that a lane's blocks are always there: admission waits on
        #: ``pool`` (the full group, which ``n_pages`` sizes) alone
        self.wpool = None
        if plan.window:
            self._wlane_pages = plan.window_lane_pages(
                max(self._round_budget, 2 * self.BLOCK_K_MENU[-1]))
            self.wpool = PagedKVPool(
                self._wlane_pages * lanes + 1, page_size, plan.window_layers,
                plan.n_kv, plan.head_dim, plan.kv_dtype, self.pool.device)
        #: the window group's turnover (``debug_state()["dispatch"]
        #: ["window"]``): pages that went back to it under a living request,
        #: the reservations that returned any, and the scheduler thread's
        #: seconds in those (inside its ``plan`` and ``dispatch`` stages)
        self._window_stats = dict(pages_released=0, releases=0,
                                  release_s=0.0)
        #: of ``walk_blocks`` and ``walk_run_blocks``, the window group's
        self._window_walk = [0, 0]
        #: the per-lane state of the layers that keep one (Mamba, Gated
        #: DeltaNet, CCA: ``spec.state_layers``; None without any):
        #: rotates through every dispatch beside ``pool.kv`` (_kv_state)
        self.state = (LaneStateStore(spec, lanes, compute_dtype,
                                     self.pool.device)
                      if plan.state_kind else None)
        #: segments the device started from zeros (a first chunk at
        #: position 0: admissions and re-prefills after preemption)
        self.zero_starts = 0
        #: the chunked form of a Mamba-2 model's rounds (``debug_state()
        #: ["ssd"]``), from the lengths a round commits: the chunks its
        #: program computed (the round's width in whole chunks) and the
        #: passes (a lane's part of one chunk) that carried a state through
        #: them, a state layer each
        self._ssd = (dict(chunks=0, passes=0)
                     if plan.state_kind == "mamba2" else None)
        #: what the request released last held (``debug_state()
        #: ["last_release"]``): a slot and pages keep their contents until
        #: another request takes them, so a check can read them there
        self.last_release: Optional[Dict[str, Any]] = None
        # unified HBM economy (tpulab.hbm, docs/PERFORMANCE.md "HBM
        # economy"): with an arbiter the batcher is the KV TENANT — the
        # pool's page store becomes elastic (a KV burst wins bytes from
        # cold models via the arbiter's pressure protocol; a hot model's
        # acquire squeezes idle KV down to the host tier), and every jit
        # this engine compiles records its scratch with the ledger.
        self.hbm = hbm
        self._hbm_reclaim_bytes = 0  # outstanding arbiter reclaim target
        self.hbm_grows = 0           # pool grow ops granted by the arbiter
        self.hbm_shrinks = 0         # pool shrink ops under pressure
        self.hbm_demotions = 0       # lanes demoted (preempted) by pressure
        #: elastic pool sizes snap to a geometric ladder off the initial
        #: size (n0, 2*n0, 4*n0, ...) — every pool shape recompiles the
        #: fused programs, so sizes must come from a bounded menu the
        #: warm-up can cover (the BLOCK_K_MENU / pow2-prefill-bucket
        #: discipline applied to capacity)
        self._hbm_pool_base = self.pool.n_pages
        self._hbm_starved_passes = 0  # hold-and-wait breaker streak
        # sharded serving (the class docstring): params placed by the
        # Megatron-TP rules (wqkv/w1/w3/lm_head column-, wo/w2
        # row-parallel), per-lane carry/state replicated
        self.mesh = plan.mesh
        param_sh = draft_sh = self._rep = None
        if self.mesh is not None:
            from tpulab.parallel.sharding import (replicate,
                                                  transformer_param_shardings)
            self._rep = replicate(self.mesh)
            param_sh = transformer_param_shardings(params, self.mesh)
            if draft_params is not None:
                draft_sh = transformer_param_shardings(draft_params,
                                                       self.mesh)
        self.params = jax.device_put(
            params, self.pool.device if param_sh is None else param_sh)
        #: the step programs (tpulab.engine.paged_steps.StepPrograms): the
        #: scheduler looks one up where it dispatches and calls it
        self.programs = StepPrograms(plan, param_sh, self.pool.kv_sharding,
                                     self._rep, hbm, draft_sh)
        #: expert layers' counters (``debug_state()["moe"]``), summed on
        #: the host from the small array every dispatch of an expert model
        #: returns and the scheduler fetches WITH the dispatch's tokens
        self._moe_shape = moe_shape(spec)
        self._moe_assignments = (
            np.zeros((self._moe_shape[0], self._moe_shape[1] - 2), np.int64)
            if self._moe_shape else None)
        self.moe_decode_steps = 0    # decode steps that had a live lane
        self.moe_experts_hit = 0     # over those steps and expert layers
        #: the indexer's work (``debug_state()["sparse"]``), host integers
        #: from the lengths the scheduler commits, by dispatch kind: rows
        #: x layers that ran the indexer, the keys they scored (a row's
        #: context), the keys they attended (``min(context, topk)``), and
        #: the rows whose context was within ``topk`` (all keys selected)
        self._sparse = ({kind: dict(query_rows=0, keys_scored=0,
                                    keys_attended=0, dense_rows=0)
                         for kind in ("decode", "round")}
                        if plan.sparse else None)
        #: host -> device and device -> host transfers the scheduler's
        #: thread made (:meth:`_put`, :meth:`_fetch`): one each a dispatch
        self.transfers: Dict[str, int] = {"h2d": 0, "d2h": 0}
        #: EVA's work (``debug_state()["eva"]``): compactions by the kind of
        #: dispatch that finished the window (a mixed round's chunk or
        #: decode row, a decode block's step), the rows they read, the
        #: pages they returned to the pool, and the scheduler thread's
        #: seconds in them (dispatch and release; the device's are the
        #: program's ``jit_paged_eva_compact`` in a capture)
        self._eva_stats = (dict(compactions={"round": 0, "decode": 0},
                                rows_compacted=0, pages_released=0,
                                compact_s=0.0)
                           if plan.eva_window else None)
        #: max fused-decode steps per dispatch (K): a K-block amortizes the
        #: host<->device round trip over K tokens.  The per-block K is
        #: adaptive (see _pick_block_k) — this is the ceiling; 1 disables
        #: multi-step dispatch entirely.
        self.decode_block = min(int(decode_block), self.BLOCK_K_MENU[-1])
        #: the carry a chain's first block passes: every lane of its buffer
        #: is ``fresh``, so only the shapes count; made once, never donated
        self._no_carry = jax.device_put(
            tuple(np.zeros((lanes,), t)
                  for t in (np.int32, np.int32, bool, np.int32)),
            self._rep or self.pool.device)
        #: the ONE dispatch in flight, un-fetched, at the top of a pass: a
        #: decode block or a mixed round (its ``kind``)
        self._pending_block: Optional[Dict[str, Any]] = None
        self._step_ewma_s = 0.0   # per-scan-step device time estimate
        self._block_fetched_t = 0.0   # return of the chain's last fetch
        # -- dispatch/sync accounting (tokens_per_dispatch telemetry and
        #    the host-syncs-per-request regression guard read these) ------
        self.decode_dispatches = 0   # device decode dispatches (any K)
        self.decode_host_syncs = 0   # blocking device->host decode fetches
        #: dispatches through the ragged kernel family: every mixed
        #: round, plus plain/spec dispatches whose attention ran the
        #: pallas ragged kernel (use_kernel)
        self.ragged_dispatches = 0
        #: per-dispatch-kind counts (the three descriptor
        #: kinds): "decode" = plain K-blocks and single ticks, "verify"
        #: = speculative draft+verify blocks, "mixed" = ragged mixed
        #: prefill+decode rounds
        self.dispatch_kinds: Dict[str, int] = {"decode": 0, "verify": 0,
                                               "mixed": 0}
        #: rows the mixed rounds computed (``M + lanes`` a round) and the
        #: rows among them that held a token: their ratio is the fill of
        #: the packed round; and the query rows their attention calls
        #: computed a layer (``M`` a lane that held a chunk, one a decoding
        #: lane): over ``mixed_tokens``, 1.0 is an attention that computes
        #: only rows that hold a token
        self.mixed_rows = 0
        self.mixed_tokens = 0
        self.mixed_attn_rows = 0
        #: the lanes that held a chunk, summed over the rounds: the K/V
        #: walk makes one call of ``M`` rows a chunk lane
        #: (``paged_steps._kv_walk``), so over ``dispatch_kinds["mixed"]``
        #: it is the rows-kernel calls a layer a round
        self.round_chunk_lanes = 0
        #: how far the round's budget engages: the prompt tokens the rounds
        #: carried (``mixed_tokens`` less the decode rows) and the rounds
        #: that spent the whole budget
        self.mixed_prompt_tokens = 0
        self.budget_rounds = 0
        #: the rounds as members of the chain: the decode rows they carried
        #: (a lane's token out of a round's weight pass), the rounds
        #: enqueued before their predecessor was fetched, and those among
        #: them whose predecessor was a round
        self.mixed_decode_rows = 0
        #: pages a key block of the attention kernels' walk holds at this
        #: engine's shapes (their own geometry), and over every decode row
        #: dispatched (a block's K steps a lane, a round's decode rows) the
        #: FULL key blocks its lane's walk read and those among them whose
        #: pages were one ascending run of ids: one DMA (:meth:`_note_walk`)
        self._walk_pages = plan.walk_block_pages
        self.walk_blocks = 0
        self.walk_run_blocks = 0
        self.ahead_rounds = 0
        self.rounds_after_round = 0
        #: sum of K over plain decode dispatches (K-blocks and single
        #: ticks): over ``dispatch_kinds["decode"]`` it is the mean block
        self.decode_block_steps = 0
        #: what the lanes of the plain decode dispatches and of the mixed
        #: rounds ran (:meth:`_note_rows`): ``passes`` a lane went through
        #: the layers (a decode step a token, a round's segment once: what
        #: reads and writes a lane state), the ``rows`` they computed and
        #: the ``keys`` at or before the last row of each pass (what an
        #: attention layer reads of the lane's pages at least)
        #: with EVA windows ``keys`` are the ROWS attended (summaries and
        #: the window's own) and ``summary_keys`` the summaries among them
        #: with window layers ``window_keys`` is what ``keys`` is on a
        #: window layer: the keys inside the window of each pass
        self.lane_work = {kind: dict(passes=0, rows=0, keys=0,
                                     **({"summary_keys": 0}
                                        if plan.eva_window else {}),
                                     **({"window_keys": 0}
                                        if plan.window else {}))
                          for kind in ("decode", "round")}
        #: the (query row, key) pairs behind the rounds' rows, exactly: a
        #: row at context ``c`` attends ``c`` keys (``lane_work["round"]``
        #: ``keys`` counts a segment once, at its last row: what is READ;
        #: this is what is COMPUTED, an attention layer; for decode steps
        #: the two are one number)
        self.round_attn_pairs = 0
        #: those pairs on a window layer: a row at context ``c`` attends
        #: ``min(c, window)`` keys (0 without window layers)
        self.round_window_pairs = 0
        #: decode blocks enqueued before their predecessor (a block or a
        #: round) was fetched (_chain_block): over
        #: ``dispatch_kinds["decode"]`` the share of blocks whose host turn
        #: the device did not wait for
        self.ahead_blocks = 0
        #: why :meth:`_chain_block` left a block or a round without a
        #: successor before its fetch, by cause, and the blocks linked after
        #: the commit instead: consumed blocks and rounds = ``ahead_blocks``
        #: + ``ahead_rounds`` + the sum of these
        self.chain_breaks: Dict[str, int] = dict.fromkeys(
            self.BREAK_CAUSES, 0)
        self.late_links = 0
        #: where a scheduler pass goes (docs/OBSERVABILITY.md "Debugz"):
        #: disjoint stages of the scheduler thread, each a ``sched.<stage>``
        #: span in a profiler capture and seconds + entries here; the same
        #: clock reads the turns the thread takes with nothing un-fetched
        #: on the device's queue, a dispatch by part, and the runs of a
        #: working stage that stood still (beside the collector's pauses,
        #: which stop this thread whichever thread collects)
        tracing.watch_collector()
        self._stages = tracing.StageClock(
            self.STAGES, prefix="sched.", turn=self.TURN_STAGES,
            causes=self.TURN_CAUSES, parts=self.DISPATCH_PARTS,
            slow_s=self.STALL_S)
        #: the blocking fetches and their seconds; those whose array was
        #: READY when the fetch began (the device had ended: the host came
        #: late, and the seconds are the copy and the interpreter lock, no
        #: wait for the device), and of those the ones of ``STALL_S`` or more
        self.fetches = {"n": 0, "s": 0.0, "ready_n": 0, "ready_s": 0.0,
                        "ready_slow_n": 0, "ready_slow_s": 0.0}
        #: request waits, summed where the observers above see them
        #: (seconds, count): submit -> prefill start, submit -> first
        #: token, first token -> second token (what a newly admitted lane
        #: waits for the running chain)
        self.queue_wait_s, self.queue_waits = 0.0, 0
        self.ttft_s, self.ttfts = 0.0, 0
        self.first_decode_wait_s, self.first_decode_waits = 0.0, 0
        # -- speculative decoding (the class docstring): ``draft_params``
        #    arms it; a lane whose rolling acceptance EWMA falls below
        #    ``spec_accept_floor`` (or whose verify dispatch trips chaos)
        #    degrades to plain blocks -------------------------------------
        self.spec_accept_floor = float(spec_accept_floor)
        self.spec_dispatches = 0        # speculative decode dispatches
        self.spec_fallbacks = 0         # lanes degraded to plain blocks
        self.spec_draft_prefills = 0    # draft-table warm-up forwards
        self.spec_tokens_drafted = 0    # proposals verified by the target
        self.spec_tokens_accepted = 0   # of those, emitted (accepted)
        self.spec_probes = 0            # probe blocks re-trying a degraded
        #                                 lane (EWMA degrades only)
        self.spec_probe_recoveries = 0  # probes whose lane stayed
        #                                 speculative (acceptance came back)
        self._spec = None if draft_params is None else {
            "params": jax.device_put(
                draft_params,
                self.pool.device if draft_sh is None else draft_sh)}
        self.prefix_cache = PrefixCache(self.pool) if prefix_cache else None
        # host-memory KV tier (tpulab.kvcache): None/False = off (zero
        # cost); True = a manager with the default host budget; an int =
        # budget bytes; a KVOffloadManager = bring-your-own (shared
        # store/transfer).  When on, preemption swaps KV device->host and
        # resume swaps back (no re-prefill), and prefix-cache eviction
        # demotes to / promotes from the host tier.
        self._owns_offload = False
        if kv_offload is None or kv_offload is False:
            self.kv_offload = None
        else:
            from tpulab.kvcache import (DEFAULT_HOST_BUDGET,
                                        KVOffloadManager)
            if isinstance(kv_offload, KVOffloadManager):
                self.kv_offload = kv_offload
            else:
                budget = (DEFAULT_HOST_BUDGET if kv_offload is True
                          else int(kv_offload))
                self.kv_offload = KVOffloadManager(self.pool, budget)
                self._owns_offload = True
        if self.kv_offload is not None and self.prefix_cache is not None:
            self.prefix_cache.on_evict = self._demote_prefix
            self.prefix_cache.promote_fn = self._promote_prefix
        # fleet KV fabric publish (tpulab.kvfabric, docs/SERVING.md
        # "Fleet KV fabric"): finished FIRST prefills export their
        # prompt-only KV to the host tier under ("fab", content_digest) —
        # the same write-behind swap_out preemption uses — plus the
        # prefill's last-position logits row under ("fablog", digest), so
        # a FetchKV RPC can serve both to the digest's routed-astray
        # fetchers without evicting this replica's own copy.  Requires
        # kv_offload (the host tier IS the export buffer: refused above
        # without one).  The round in which a first prompt ends publishes
        # (:meth:`_consume_round`) and is not chained ahead, so the
        # snapshot's gather goes before any decode write into the tail page.
        self.kv_publish = bool(kv_publish)
        self._fab_handles: "Dict[bytes, Any]" = OrderedDict()
        self._fab_lock = threading.Lock()
        self.kv_publishes = 0  # prompt snapshots exported to the fabric
        #: rolling prefill throughput (tokens/s, EWMA) — the fabric's
        #: cost gate weighs a remote fetch's wire time against simply
        #: recomputing the prompt here (0.0 until the first prefill)
        self.prefill_ewma_tok_s = 0.0
        #: optional tpulab.utils.tracing.ChromeTraceRecorder — the batcher
        #: records queue/prefill/decode-chunk spans per request (spans ride
        #: per-lane rows; the serving layer may attach one post-hoc)
        self.trace = trace
        #: optional tpulab.utils.metrics.GenerationMetrics — TTFT /
        #: inter-token / queue-wait / e2e distributions observed per
        #: completed request at the source, not polled
        self.metrics = metrics
        #: optional tpulab.obs.FlightRecorder — per-request wide events
        #: (docs/OBSERVABILITY.md "Flight recorder").  Armed, each request
        #: carries a small detail dict (block sizes, ITL samples, swap
        #: events, peak pages) and completion attaches the summary to the
        #: future as ``_tpulab_flight``; requests whose wide event the RPC
        #: layer assembles (flight_owner="rpc") are never double-recorded.
        #: None = disarmed: one None check per site, tokens unchanged
        #: either way (the recorder observes, never steers).
        self.flight = flight
        #: debugz on-demand XLA profiler capture (arm_profile): dict with
        #: remaining/dir/active, managed by the scheduler thread only
        self._profile: Optional[Dict[str, Any]] = None
        self._queue: List[_PagedRequest] = []
        self._requests: Dict[Future, _PagedRequest] = {}
        self._active: List[Optional[_PagedRequest]] = [None] * lanes
        self._admit_counter = 0
        self.preemptions = 0
        #: of those, evictions of BATCH-class lanes (the offline lane is
        #: the first preemption victim by design — a high number here
        #: with few online preemptions means the lane is doing its job)
        self.batch_preemptions = 0
        if self.hbm is not None:
            # register as the KV tenant AFTER kv_offload is settled (the
            # reclaimable estimate reads it) and claim the page store's
            # tracked bytes — the ledger now mirrors the allocator gauge
            from tpulab.hbm import KV_TENANT
            self.hbm.register(KV_TENANT, reclaim=self._hbm_reclaim,
                              reclaimable=self._hbm_reclaimable,
                              gauge=lambda: self.pool.hbm_bytes)
            self.hbm.mirror_claim(KV_TENANT, "pool", self.pool.hbm_bytes)
        self.completed_requests = 0  # futures resolved successfully
        self.tokens_generated = 0    # emitted across all requests
        self._cv = threading.Condition()
        self._shutdown = False
        # every step program is traced and lowered on this thread
        self._thread = threading.Thread(
            target=on_one_frame_chunk, args=(self._run,), name="cbatch",
            daemon=True)
        self._thread.start()

    @property
    def _kv_state(self):
        """What the step programs take as ``kv_pool``, donate and return:
        the page store, or with a lane state the pair ``(page store, lane
        state)``, with an indexer ``(page store, index rows)``, with window
        layers the store's two groups ``(full, window)``."""
        if self.state is not None:
            return self.pool.kv, self.state.arrays
        if self.pool.index is not None:
            return self.pool.kv, self.pool.index
        if self.wpool is not None:
            return self.pool.kv, self.wpool.kv
        return self.pool.kv

    @_kv_state.setter
    def _kv_state(self, value) -> None:
        if self.state is not None:
            self.pool.kv, self.state.arrays = value
        elif self.pool.index is not None:
            self.pool.kv, self.pool.index = value
        elif self.wpool is not None:
            self.pool.kv, self.wpool.kv = value
        else:
            self.pool.kv = value

    def _note_rows(self, kind: str, start: int, n: int) -> None:
        """Count ``n`` query rows of one lane at contexts ``start + 1 ..
        start + n`` (keys at or before the row, itself included) under
        ``kind``: "decode" (a row a step) or "round" (a segment of a mixed
        round) in ``lane_work``; with an indexer also what it scored and
        selected."""
        if n <= 0:
            return
        w = self.lane_work[kind]
        if self.plan.eva_window:
            # the rows of one window (no dispatch crosses a boundary): the
            # keys of a row are the rows of the table at or before it
            done = start // self.plan.eva_window
            summaries = done * self.model_spec.eva_summaries
            start -= done * self.plan.eva_saved
            w["summary_keys"] += summaries * (n if kind == "decode" else 1)
        triangle = n * start + n * (n + 1) // 2
        w["passes"] += n if kind == "decode" else 1
        w["rows"] += n
        w["keys"] += triangle if kind == "decode" else start + n
        if kind == "round":
            self.round_attn_pairs += triangle
        if self.plan.window:
            # a window layer's row at context c attends min(c, window) keys;
            # a segment reads the keys from its first row's window on
            win = self.plan.window
            short = min(max(win - start, 0), n)     # rows with c <= window
            pairs = (short * start + short * (short + 1) // 2
                     + (n - short) * win)
            w["window_keys"] += (pairs if kind == "decode"
                                 else min(start + n, win + n - 1))
            if kind == "round":
                self.round_window_pairs += pairs
        if self._sparse is None:
            return
        k, layers = self.model_spec.index_topk, self.model_spec.n_layers
        dense = min(max(k - start, 0), n)       # rows whose context <= k
        attended = (dense * start + dense * (dense + 1) // 2
                    + (n - dense) * k)
        c = self._sparse[kind]
        c["query_rows"] += n * layers
        c["keys_scored"] += triangle * layers
        c["keys_attended"] += attended * layers
        c["dense_rows"] += dense * layers

    def _rows(self, req: _PagedRequest, n: int) -> int:
        """The rows of ``req``'s page table that hold its positions ``[0,
        n)``, ``n`` inside the window it is in: ``n`` itself, less what its
        compacted EVA windows gave back."""
        return n - req.eva_done * self.plan.eva_saved

    def _note_walk(self, reqs, steps: int = 1) -> None:
        """Count the key blocks ``steps`` decode rows of each of ``reqs``
        walk (``debug_state()["pool"]``): the full blocks under the lane's
        last position as the host knows it, by the kernels' geometry."""
        g, ps = self._walk_pages, self.page_size
        for req in reqs:
            full = min(self._rows(req, req.length) // ps + 1,
                       len(req.pages)) // g
            self.walk_blocks += steps * full
            self.walk_run_blocks += steps * req.run_blocks(g, full)
            if self.wpool is not None:
                # the window group's walk: the full blocks from the one that
                # holds the oldest visible key on, each taken as one grant
                first = max(req.length - self.plan.window + 1, 0) // (g * ps)
                blocks = [req.wpages[i:i + g] for i in range(
                    max(first * g - req.wfirst, 0), full * g - req.wfirst,
                    g)]
                # (a grant's ids ascend: one run iff its ends are g - 1 apart)
                runs = sum(len(blk) == g and blk[-1] - blk[0] == g - 1
                           for blk in blocks)
                self.walk_blocks += steps * len(blocks)
                self.walk_run_blocks += steps * runs
                self._window_walk[0] += steps * len(blocks)
                self._window_walk[1] += steps * runs

    def _boundary(self, req: _PagedRequest) -> int:
        """The first position ``req`` may not take in before a compaction:
        the end of its EVA window (without EVA, no such position)."""
        if not self.plan.eva_window:
            return 1 << 62
        return (req.eva_done + 1) * self.plan.eva_window

    def _peak_rows(self, req: _PagedRequest, total: int) -> int:
        """The most rows ``req`` holds on its way to ``total`` positions."""
        if not self.plan.eva_window:
            return total
        return self.model_spec.cache_rows_peak(total, req.eva_done)

    def _window_pages(self, req: _PagedRequest, lo: int, hi: int) -> None:
        """Move ``req``'s table in the page store's WINDOW group to the key
        blocks that overlap ``(lo - window, hi]``: ``lo`` the lowest
        position the dispatch about to be made may hold a query row at (the
        lane's committed length: what is in flight only moves it up), ``hi``
        the last position it may write.  Blocks wholly behind ``lo -
        window`` go back to the group NOW, under the living request, and
        the blocks up to ``hi`` are taken, a block a grant (its ids one
        run: the walk reads it as one copy).  Nothing is rewritten: the
        table moves.

        Why a returned page may be handed to another lane at once: a
        dispatched program holds the TABLE it was given (the tables are
        fields of its one packed buffer), so the programs in flight still
        read the page through theirs; the page's next owner writes it in a
        program dispatched LATER, and the programs of one device run in the
        order they were dispatched, so every read of the old owner's rows
        is done before the first write of the new owner's.  A later program
        of the old owner never sees the page: its table starts behind it,
        and its walk and mask start at ``row position - window + 1``.

        The group is sized so that the blocks are always there
        (``EnginePlan.window_lane_pages``): admission never waits on it."""
        g, ps, win = self._walk_pages, self.page_size, self.plan.window
        first = max(lo - win + 1, 0) // (g * ps) * g
        if first > req.wfirst:
            t0 = _time.perf_counter()
            n = min(first - req.wfirst, len(req.wpages))
            freed, req.wpages = req.wpages[:n], req.wpages[n:]
            req.wfirst = first
            if freed:
                self.wpool.release_pages(freed)
                ws = self._window_stats
                ws["pages_released"] += len(freed)
                ws["releases"] += 1
                ws["release_s"] += _time.perf_counter() - t0
        end = (hi // (g * ps) + 1) * g
        while req.wfirst + len(req.wpages) < end:
            pages = self.wpool.allocate_pages(g)
            if pages is None:
                raise RuntimeError(
                    "the page store's window group has no free block for a "
                    f"lane that holds {len(req.wpages)} of its "
                    f"{self._wlane_pages} pages: the group is sized so that "
                    "this cannot be")
            req.wpages.extend(pages)

    def _lane_tables(self, lanes_reqs) -> Dict[str, np.ndarray]:
        """The table fields of a dispatch's buffer, ``tables`` (and, with
        window layers, ``wtables``: the window group's) with the rows of
        ``lanes_reqs`` ``[(lane, req), ...]`` filled in: entry ``p //
        page_size`` is position ``p``'s page."""
        out = {"tables": np.zeros((self.lanes, self.max_pages), np.int32)}
        if self.wpool is not None:
            out["wtables"] = np.zeros_like(out["tables"])
        for lane, req in lanes_reqs:
            out["tables"][lane, :len(req.pages)] = req.pages
            if self.wpool is not None:
                out["wtables"][lane, req.wfirst:req.wfirst
                               + len(req.wpages)] = req.wpages
        return out

    def _eva_compact(self) -> None:
        """Compact every lane that stands at the end of an EVA window: its
        window's ``eva_window`` rows become ``eva_summaries`` rows in place
        (:func:`paged_eva_compact`, one lane a dispatch, never fetched: the
        device's queue is in order, so the next program reads the summaries),
        and the pages behind the lane's new last row go back to the pool,
        less what the rest of its prompt will need at its widest.  Called
        where no program that writes the lane's rows can be in flight: at
        the top of a pass and before a decode plan, for the lanes outside
        the block or round dispatched ahead (whose lanes the chain holds
        short of their boundary: it breaks, cause ``compact``, where one
        reaches it, :meth:`_chain_block`)."""
        w, ps, spec = self.plan.eva_window, self.page_size, self.model_spec
        chained = (self._pending_block["lane_reqs"]
                   if self._pending_block is not None else ())
        with self._cv:
            due = [(lane, req) for lane, req in enumerate(self._active)
                   if req is not None and not req.cancelled
                   and lane not in chained
                   and req.length >= self._boundary(req)]
        for lane, req in due:
            t0 = _time.perf_counter()
            with stage(self._stages, "dispatch"):
                first = req.eva_done * (spec.eva_summaries // ps)
                pages = np.asarray(req.pages[first:first + w // ps], np.int32)
                self._kv_state = self.programs.compact(
                    self.params, self._kv_state, self._put(pages))
                self._stages.launched()
            with self._cv:
                req.eva_done += 1
                keep = (self._peak_rows(req, req.length + len(
                    req.pending_prompt)) + ps - 1) // ps
                freed, req.pages = req.pages[keep:], req.pages[:keep]
                self.pool.release_pages(freed)
                e = self._eva_stats
                e["compactions"]["round" if req.length <= len(req.prompt)
                                 else "decode"] += 1
                e["rows_compacted"] += w
                e["pages_released"] += len(freed)
                dur = _time.perf_counter() - t0
                e["compact_s"] += dur
            self._span("eva.compact", lane, t0, dur, req,
                       window=req.eva_done - 1, rows_in=w,
                       rows_out=spec.eva_summaries, pages_released=len(freed))

    def _put(self, host):
        """ONE host -> device transfer, counted: ``host`` (a numpy array)
        on the engine's device, replicated under a mesh."""
        import jax
        self.transfers["h2d"] += 1
        return jax.device_put(host, self._rep or self.pool.device)

    def _fetch(self, dev) -> np.ndarray:
        """ONE blocking device -> host fetch, counted, by whether the
        device had already ended when it began."""
        self.transfers["d2h"] += 1
        f = self.fetches
        ready = dev.is_ready()
        t0 = _time.perf_counter()
        host = np.asarray(dev)
        dt = _time.perf_counter() - t0
        f["n"] += 1
        f["s"] += dt
        if ready:
            f["ready_n"] += 1
            f["ready_s"] += dt
            if dt >= self.STALL_S:
                f["ready_slow_n"] += 1
                f["ready_slow_s"] += dt
        return host

    def _results(self, out, k: Optional[int] = None, spec: bool = False):
        """A dispatch's one result array, fetched and taken apart
        (``result_fields``)."""
        return unpack_words(
            result_fields(self.lanes, k, None if spec else self._moe_shape,
                          spec), self._fetch(out))

    #: the stages of a scheduler pass, in the order a pass takes them
    STAGES = ("admit", "plan", "dispatch", "fetch", "commit", "emit", "idle")
    #: the stages that are work (not waiting): what a turn is made of
    TURN_STAGES = ("admit", "plan", "dispatch", "commit", "emit")
    #: one run of a working stage this long is a stall (``debug_state()``
    #: ``["dispatch"]["host"]["stalls"]``): four times the longest mean
    #: stage of any benchmark cell's turn (5.06 ms of emit, PR 51's ledger)
    STALL_S = 0.02
    #: why a decode block or a mixed round got no successor before its
    #: fetch (:meth:`_chain_block`)
    BREAK_CAUSES = ("k1", "shutdown_or_reclaim", "released", "completion",
                    "joiner", "spec", "k", "pages", "compact", "host",
                    "resumed", "stops")
    #: what opens a turn: a break cause that is a span name of its own, a
    #: mixed round's fetch, a single tick's; everything else is ``other``
    TURN_CAUSES = ("completion", "joiner", "round", "k", "pages",
                   "released", "single", "compact")
    #: the parts of a decode block's and a mixed round's ``dispatch``
    #: stage: host arrays, their transfer, the jitted call
    DISPATCH_PARTS = ("dispatch.arrays", "dispatch.put", "dispatch.call")

    # -- public -------------------------------------------------------------
    def submit(self, prompt, steps: int, on_token=None,
               sampling: Optional[SamplingParams] = None,
               priority: int = 0, stop_tokens=None,
               logprobs: bool = False, deadline=None,
               trace_id: Optional[str] = None,
               export_digest: Optional[bytes] = None,
               tenant: Optional[str] = None,
               flight_owner: Optional[str] = None,
               request_class: str = "online") -> Future:
        """``on_token(token, index)`` (optional) streams tokens as they
        decode — the hook the Generate RPC rides for paged serving.
        ``sampling`` selects the token policy (default greedy).
        ``logprobs=True`` resolves the future to ``(tokens, logprobs)``
        (each token's chosen log-probability, computed on device) instead
        of the plain token list, and ``on_token`` is then called with a
        third ``logprob`` argument.
        ``stop_tokens`` (iterable of token ids, e.g. the tokenizer's EOS)
        ends generation early: the stop token is emitted as the final
        token and the lane/pages free at that tick.
        ``priority`` orders admission (higher first; FIFO within a class)
        and arms preemption: a queued request strictly outranking an active
        one evicts it — the victim's pages free immediately and it resumes
        later by re-prefilling prompt+generated (exact-token resume; with a
        prefix cache the recompute mostly hits cached pages).
        ``deadline`` (a :class:`~tpulab.core.deadline.Deadline` or a float
        budget in seconds) bounds the request: the scheduler cancels it
        before its next step once expired — lane and KV pages free within
        one tick — and the future fails with DeadlineExceeded.
        ``trace_id`` tags this request's queue/prefill/decode spans in the
        attached ``trace`` recorder (the Generate RPC threads the client's
        id through here, merging both processes into one timeline).
        ``export_digest`` (requires ``kv_offload``) demotes the finished
        request's KV to the host tier under ``("ship", digest)`` at lane
        release — the prefill-replica half of disaggregated serving
        (tpulab.disagg): submit with ``steps=1`` and the resulting
        snapshot covers exactly the prompt; the export
        :class:`~tpulab.kvcache.offload.SwapHandle` lands on the future
        as ``_tpulab_kv_export`` (None when the swap degraded).
        ``tenant`` tags the request for flight-recorder / debugz
        attribution (never read by the scheduler); ``flight_owner="rpc"``
        marks the wide event as assembled by the RPC layer — the engine
        still attaches its completion summary to the future
        (``_tpulab_flight``) but does not record it itself.
        ``request_class`` ("online" default, or "batch" — the offline
        batch lane, docs/SERVING.md) ranks the request: a batch request
        queues behind EVERY online request regardless of priority, is
        the first preemption victim when an online arrival needs its
        lane or pages, and its ``on_token`` hook (a checkpoint sink,
        not an interactive consumer) never drags the fused-decode block
        size down."""
        if request_class not in ("online", "", "batch"):
            raise ValueError(f"unknown request_class {request_class!r} "
                             "(want 'online' or 'batch')")
        flat = np.asarray(prompt).reshape(-1)
        if isinstance(deadline, Deadline):
            deadline = deadline.expiry
        elif deadline is not None:
            deadline = _time.monotonic() + float(deadline)
        n_prompt = len(flat)
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        if n_prompt + steps > self.max_len:
            raise ValueError(f"prompt+steps exceeds max_len {self.max_len}")
        if flat.min() < 0 or flat.max() >= self.vocab:
            # XLA gather CLAMPS out-of-bounds ids — silent garbage tokens;
            # reject at the host boundary instead
            raise ValueError(f"prompt token ids outside [0, {self.vocab})")
        if export_digest is not None and self.kv_offload is None:
            raise ValueError("export_digest requires kv_offload")
        req = _PagedRequest(prompt, steps, on_token=on_token,
                            sampling=sampling, priority=priority,
                            stop_tokens=stop_tokens, logprobs=logprobs,
                            deadline=deadline, trace_id=trace_id,
                            tenant=tenant,
                            batch=request_class == "batch")
        req.export_digest = export_digest
        if self.flight is not None or flight_owner:
            self._fl_arm(req, flight_owner)
        with self._cv:
            if self._shutdown:
                raise RuntimeError("ContinuousBatcher is shut down")
            self._enqueue_locked(req, front_of_class=False)
            self._requests[req.future] = req
            self._cv.notify()
        return req.future

    def submit_shipped(self, prompt, steps: int, first_token: int,
                       handle, on_token=None,
                       sampling: Optional[SamplingParams] = None,
                       priority: int = 0, stop_tokens=None, deadline=None,
                       trace_id: Optional[str] = None,
                       tenant: Optional[str] = None,
                       flight_owner: Optional[str] = None) -> Future:
        """Admit a request whose prompt KV arrived SHIPPED from a prefill
        replica (tpulab.disagg) — the decode-replica half of
        disaggregated serving.

        ``handle`` is the resident host-tier snapshot a
        :class:`~tpulab.disagg.KVShipper` import minted (None = shipment
        lost: the request still admits and prefills locally), and
        ``first_token`` the prefill replica's index-0 pick — emitted to
        ``on_token`` here (index 0) so the stream the consumer sees is
        identical to a unified replica's.  Admission promotes the
        snapshot through the existing ``KVOffloadManager.restore`` path:
        the lane starts decoding with ZERO prefill dispatches.  Every
        degraded shipment (lost, corrupt, chaos-tripped, budget-refused,
        restore failure) falls back to the exact local prefill — which
        recomputes the same KV, so token parity holds either way.

        Host-sampled requests (``temperature > 0`` without device
        sampling) are rejected: their PRNG stream is keyed by draw
        order, which does not survive the replica hop; greedy and
        device-sampled streams are keyed by (seed, position) and do."""
        flat = np.asarray(prompt).reshape(-1)
        if isinstance(deadline, Deadline):
            deadline = deadline.expiry
        elif deadline is not None:
            deadline = _time.monotonic() + float(deadline)
        n_prompt = len(flat)
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        if n_prompt + steps > self.max_len:
            raise ValueError(f"prompt+steps exceeds max_len {self.max_len}")
        if flat.min() < 0 or flat.max() >= self.vocab:
            raise ValueError(f"prompt token ids outside [0, {self.vocab})")
        if not 0 <= int(first_token) < self.vocab:
            raise ValueError(
                f"shipped first token outside [0, {self.vocab})")
        sp = sampling or SamplingParams()
        if sp.temperature > 0.0 and not sp.device:
            raise ValueError(
                "shipped-KV admission requires greedy or device sampling "
                "(host-side PRNG streams do not survive the replica hop)")
        if handle is not None and self.kv_offload is None:
            raise ValueError("shipped-KV admission requires kv_offload")
        if handle is not None and handle.length != n_prompt:
            raise ValueError(
                f"shipment covers {handle.length} positions, prompt has "
                f"{n_prompt}")
        req = _PagedRequest(prompt, steps, on_token=on_token,
                            sampling=sp, priority=priority,
                            stop_tokens=stop_tokens, deadline=deadline,
                            trace_id=trace_id, tenant=tenant)
        if self.flight is not None or flight_owner:
            self._fl_arm(req, flight_owner)
        # the first-token pick already happened on the prefill replica:
        # seed the lane as a resume (a degraded restore then re-prefills
        # and DISCARDS its logits, exactly like a preemption resume)
        req.tokens_out.append(int(first_token))
        req.kv_handle = handle
        req.resumed = True
        self.tokens_generated += 1
        self._emit(req, int(first_token), 0, None)
        if req.finished():  # steps == 1 or first token hit a stop token
            if handle is not None and self.kv_offload is not None:
                self.kv_offload.discard(handle)
            req.kv_handle = None
            self._flight_complete(req)
            req.future.set_result(self._result_of(req))
            self.completed_requests += 1
            return req.future
        with self._cv:
            if self._shutdown:
                raise RuntimeError("ContinuousBatcher is shut down")
            self._enqueue_locked(req, front_of_class=False)
            self._requests[req.future] = req
            self._cv.notify()
        return req.future

    def cancel(self, future: Future) -> None:
        """Abort a submitted request (freed at the next tick boundary)."""
        with self._cv:
            req = self._requests.get(future)
            if req is not None:
                req.cancelled = True
                if req in self._queue:  # never started: finish immediately
                    self._queue.remove(req)
                    self._requests.pop(future, None)
                    self._discard_handle(req)
        if req is not None and req not in self._active and not future.done():
            future.cancel()

    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify()
        self._thread.join(timeout=30)
        if not self._thread.is_alive() and self.prefix_cache is not None:
            self.prefix_cache.on_evict = None  # shutdown clear != pressure
            self.prefix_cache.clear()  # release the cache's page refs
        if self._owns_offload and not self._thread.is_alive():
            self.kv_offload.close()  # drain write-behind, free host tier
        if self.state is not None and not self._thread.is_alive():
            self.state.close()
        if self._owns_pool and not self._thread.is_alive():
            self.pool.close()  # free the page stores' HBM eagerly
            if self.wpool is not None:
                self.wpool.close()
            if self.hbm is not None:
                from tpulab.hbm import KV_TENANT
                self.hbm.release(KV_TENANT, "pool")

    @property
    def active_lanes(self) -> int:
        with self._cv:
            return sum(r is not None for r in self._active)

    @property
    def queued_requests(self) -> int:
        with self._cv:
            return len(self._queue)

    @property
    def decode_holdings(self):
        """``(pages, positions)`` the decoding lanes hold right now: a cheap
        gauge of what a position of context costs (with EVA windows a
        lane's finished windows are summaries, so pages grow slower than
        positions)."""
        with self._cv:
            held = [(len(r.pages), r.length) for r in self._active
                    if r is not None and not r.pending_prompt]
        return sum(p for p, _ in held), sum(n for _, n in held)

    @property
    def decode_window_pages(self) -> int:
        """The pages of the page store's WINDOW group the decoding lanes
        hold right now (0 without window layers): beside
        :attr:`decode_holdings`, what a position costs a lane whose window
        layers keep their window alone."""
        with self._cv:
            return sum(len(r.wpages) for r in self._active
                       if r is not None and not r.pending_prompt)

    @property
    def spec_acceptance(self) -> float:
        """Lifetime draft acceptance rate (accepted / drafted)."""
        return self.spec_tokens_accepted / max(1, self.spec_tokens_drafted)

    @property
    def admission_cost_factor(self) -> float:
        """Cost multiplier the admission frontend applies to this
        engine's requests (serving/admission.py).  A speculative request
        holds a SECOND page table (the draft KV) next to the target's
        and burns draft+verify compute on rejected proposals —
        drafted-but-rejected tokens are not free, so cost-aware
        admission must not plan capacity as if they were."""
        return 2.0 if self._spec is not None else 1.0

    # -- telemetry (no-ops without an attached recorder/metrics) ------------
    def _span(self, name: str, lane: int, t0: float, dur: float,
              req: _PagedRequest, **extra) -> None:
        """One request-lifecycle span on the lane's trace row."""
        tr = self.trace
        if tr is None:
            return
        if req.trace_id:
            extra["trace_id"] = req.trace_id
        tr.add_span(name, t0, dur, tid=lane, lane=lane, **extra)

    def _flush_decode_chunk(self, req: _PagedRequest, lane: int,
                            now: float, **extra) -> None:
        """Close the open decode-chunk span at ``now`` and start the next
        (K>1 dispatch passes ``block=K`` — block-sized decode spans)."""
        n = len(req.tokens_out)
        if req.chunk_t0 is not None and n > req.chunk_start:
            self._span("decode", lane, req.chunk_t0, now - req.chunk_t0,
                       req, first=req.chunk_start,
                       tokens=n - req.chunk_start, **extra)
        req.chunk_t0 = now
        req.chunk_start = n

    def _note_complete(self, req: _PagedRequest) -> None:
        if self.metrics is not None:
            self.metrics.observe_e2e(_time.perf_counter() - req.t_submit)

    # -- flight recorder (tpulab.obs, docs/OBSERVABILITY.md) ----------------
    #: per-request detail lists stay bounded — a pathological request
    #: must not turn its own wide event into a memory leak
    FLIGHT_DETAIL_CAP = 1024

    @staticmethod
    def _fl_arm(req: _PagedRequest, owner: Optional[str]) -> None:
        """Attach the per-request flight detail dict (armed path only)."""
        req.fl = {"owner": owner, "blocks": [], "itl": [],
                  "swap_outs": 0, "swap_ins": 0, "preempts": 0,
                  "pages_peak": 0, "chaos0": chaos.fired_snapshot()}

    def _fl_block(self, req: _PagedRequest, k: int, n: int,
                  dt: Optional[float]) -> None:
        """One fused-decode dispatch's contribution to the wide event:
        block size K, tokens emitted, the spread per-token latency."""
        fl = req.fl
        if fl is None:
            return
        if len(fl["blocks"]) < self.FLIGHT_DETAIL_CAP:
            fl["blocks"].append((k, n))
        if dt is not None and len(fl["itl"]) < self.FLIGHT_DETAIL_CAP:
            fl["itl"].append((dt, n))
        pages = len(req.pages) + len(req.draft_pages)
        if pages > fl["pages_peak"]:
            fl["pages_peak"] = pages

    def _fl_pages(self, req: _PagedRequest) -> None:
        fl = req.fl
        if fl is not None:
            pages = len(req.pages) + len(req.draft_pages)
            if pages > fl["pages_peak"]:
                fl["pages_peak"] = pages

    def _flight_summary(self, req: _PagedRequest,
                        outcome: str) -> Dict[str, Any]:
        """The engine's half of the wide event (the RPC layer adds
        admission/status/transport fields for requests it owns)."""
        now = _time.perf_counter()
        ev: Dict[str, Any] = {
            "kind": "paged", "outcome": outcome, "tenant": req.tenant,
            "request_class": "batch" if req.batch else "online",
            "priority": req.priority, "trace_id": req.trace_id,
            "prompt_tokens": int(len(req.prompt)), "steps": req.steps,
            "tokens": len(req.tokens_out),
            "t_submit": req.t_submit, "t_prefill0": req.t_prefill0,
            "t_first": req.t_first, "t_last": req.t_last,
            "e2e_s": now - req.t_submit, "lane": req.lane,
            "pages": len(req.pages),
        }
        if req.t_prefill0 is not None:
            ev["queue_wait_s"] = req.t_prefill0 - req.t_submit
        if req.t_first is not None:
            ev["ttft_s"] = req.t_first - req.t_submit
        if req.spec_drafted:
            ev["spec_drafted"] = req.spec_drafted
            ev["spec_accepted"] = req.spec_accepted
            ev["spec_acceptance"] = round(
                req.spec_accepted / req.spec_drafted, 4)
        fl = req.fl
        if fl is not None:
            ev["pages_peak"] = max(fl["pages_peak"], len(req.pages))
            ev["block_ks"] = [k for k, _n in fl["blocks"]]
            ev["preempts"] = fl["preempts"]
            ev["swap_outs"] = fl["swap_outs"]
            ev["swap_ins"] = fl["swap_ins"]
            if fl["itl"]:
                itl = np.repeat([d for d, _ in fl["itl"]],
                                [n for _, n in fl["itl"]])
                ev["itl_ms"] = {
                    "p50": round(float(np.percentile(itl, 50)) * 1e3, 4),
                    "p99": round(float(np.percentile(itl, 99)) * 1e3, 4),
                    "max": round(float(itl.max()) * 1e3, 4),
                    "n": int(itl.size)}
            trips = {}
            for point, n in chaos.fired_snapshot().items():
                d = n - fl["chaos0"].get(point, 0)
                if d > 0:
                    trips[point] = d
            if trips:
                ev["chaos_trips"] = trips
        if self.hbm is not None:
            ev["hbm_pressure_events"] = self.hbm.pressure_events
        return ev

    def _flight_complete(self, req: _PagedRequest,
                         outcome: str = "SUCCESS") -> None:
        """Completion hook (every future-resolution site): attach the
        engine summary to the future BEFORE it resolves (race-free, the
        ``_tpulab_compute_s`` idiom) and record it — unless the RPC layer
        owns this request's wide event."""
        fr = self.flight
        if fr is None and req.fl is None:
            return
        ev = self._flight_summary(req, outcome)
        req.future._tpulab_flight = ev
        owner = req.fl.get("owner") if req.fl is not None else None
        if fr is not None and owner != "rpc":
            fr.observe(ev)

    # -- debugz (tpulab.obs.debugz) -----------------------------------------
    def arm_profile(self, ticks: int, log_dir: Optional[str] = None) -> str:
        """Arm the process's profiler switch (``tracing.start``) around the
        next ``ticks`` scheduler ticks (the Debug RPC's ``profile_ticks``).
        The capture starts at the next pass the scheduler runs and stops
        after ``ticks`` passes; returns the trace directory
        (``tensorboard --logdir`` it).  Raises ``tracing.ProfilerBusy``
        while another owner's capture is open."""
        if int(ticks) < 1:
            raise ValueError("profile_ticks must be >= 1")
        if tracing.active():
            raise tracing.ProfilerBusy(
                "a profiler capture is already open in this process")
        if log_dir is None:
            import tempfile
            log_dir = tempfile.mkdtemp(prefix="tpulab-profile-")
        with self._cv:
            if self._profile is not None:
                raise RuntimeError("a profiler capture is already armed")
            self._profile = {"remaining": int(ticks), "dir": log_dir,
                             "active": False}
            self._cv.notify()
        return log_dir

    def _profile_step(self, done: bool = False) -> None:
        """Scheduler-thread profiler bookkeeping: start the armed capture,
        count one pass, stop at zero (or at shutdown with ``done``)."""
        prof = self._profile
        if prof is None:
            return
        if done:
            if prof["active"]:
                tracing.stop()
            self._profile = None
            return
        if not prof["active"]:
            try:
                tracing.start(prof["dir"])
            except tracing.ProfilerBusy:
                # another owner opened a capture since arm_profile: theirs
                # stands, this one is dropped (debugz shows it disarmed)
                self._profile = None
                return
            prof["active"] = True
            return  # the NEXT ticks are captured; arming pass is free
        prof["remaining"] -= 1
        if prof["remaining"] <= 0:
            tracing.stop()
            self._profile = None

    def debug_state(self) -> Dict[str, Any]:
        """Live scheduler introspection for debugz (one consistent
        snapshot under the scheduler lock): lanes, queue, elastic pool +
        ladder position, dispatch counters, speculative and prefix-cache
        state."""
        now = _time.perf_counter()
        with self._cv:
            lanes = []
            for lane, req in enumerate(self._active):
                if req is None:
                    lanes.append({"lane": lane, "state": "idle"})
                    continue
                lanes.append({
                    "lane": lane,
                    "state": ("prefill" if req.pending_prompt
                              else "decode"),
                    "request_class": "batch" if req.batch else "online",
                    "tenant": req.tenant, "priority": req.priority,
                    "trace_id": req.trace_id,
                    "age_s": round(now - req.t_submit, 6),
                    "tokens": len(req.tokens_out), "steps": req.steps,
                    "prompt_tokens": int(len(req.prompt)),
                    "pages": len(req.pages),
                    "draft_pages": len(req.draft_pages),
                    "cancelled": req.cancelled,
                    "length": req.length, "eva_done": req.eva_done,
                    **({"window_pages": len(req.wpages),
                        "window_first": req.wfirst}
                       if self.wpool is not None else {}),
                })
            queue_head = [{"tenant": q.tenant, "priority": q.priority,
                           "age_s": round(now - q.t_submit, 6),
                           "prompt_tokens": int(len(q.prompt)),
                           "steps": q.steps}
                          for q in self._queue[:16]]
            queued = len(self._queue)
            profile_armed = self._profile is not None
            last_release = self.last_release
        pool = self.pool
        rung, size = 0, self._hbm_pool_base
        while size and size * 2 <= pool.n_pages:
            size *= 2
            rung += 1
        out: Dict[str, Any] = {
            "kind": "paged",
            "lanes": lanes,
            "queued_requests": queued,
            "queue_head": queue_head,
            "pool": {"n_pages": pool.n_pages,
                     "free_pages": pool.free_pages,
                     "page_size": pool.page_size,
                     "page_nbytes": pool.page_nbytes,
                     "entry_kind": pool.entry_kind,
                     "bytes_per_token": pool.bytes_per_token,
                     "index_bytes_per_token": pool.index_bytes_per_token,
                     "hbm_bytes": pool.hbm_bytes,
                     "n_shards": pool.n_shards,
                     "elastic": self.hbm is not None,
                     "ladder_base": self._hbm_pool_base,
                     "ladder_rung": rung,
                     "grows": self.hbm_grows,
                     "shrinks": self.hbm_shrinks,
                     "walk_block_pages": self._walk_pages,
                     "walk_blocks": self.walk_blocks,
                     "walk_run_blocks": self.walk_run_blocks},
            "dispatch": {"decode_block": self.decode_block,
                         "decode_dispatches": self.decode_dispatches,
                         "decode_host_syncs": self.decode_host_syncs,
                         # the benchmark's names (see ``__init__``)
                         "prefill_dispatches": 0,
                         "ragged": True,
                         "use_kernel": self.use_kernel,
                         "ragged_dispatches": self.ragged_dispatches,
                         "kinds": dict(self.dispatch_kinds),
                         "transfers": dict(self.transfers),
                         "mixed_rows": self.mixed_rows,
                         "mixed_tokens": self.mixed_tokens,
                         "mixed_attn_rows": self.mixed_attn_rows,
                         "round_chunk_lanes": self.round_chunk_lanes,
                         "round_budget": self._round_budget,
                         "round_budget_why": self.round_budget_why,
                         "latent_tile": self.plan.latent_tile,
                         "mixed_prompt_tokens": self.mixed_prompt_tokens,
                         "budget_rounds": self.budget_rounds,
                         "mixed_decode_rows": self.mixed_decode_rows,
                         "ahead_rounds": self.ahead_rounds,
                         "rounds_after_round": self.rounds_after_round,
                         "decode_block_steps": self.decode_block_steps,
                         "lane_work": {kind: dict(w) for kind, w
                                       in self.lane_work.items()},
                         "round_attn_pairs": self.round_attn_pairs,
                         **({"round_window_pairs": self.round_window_pairs,
                             "window": dict(
                                 self._window_stats, keys=self.plan.window,
                                 lane_pages=self._wlane_pages)}
                            if self.wpool is not None else {}),
                         "ahead_blocks": self.ahead_blocks,
                         "chain": {"breaks": dict(self.chain_breaks),
                                   "late_links": self.late_links},
                         "stages": self._stages.stages(),
                         "turns": self._stages.turns(),
                         "host": {"gc": tracing.collector_pauses(),
                                  "stalls": self._stages.stalls()},
                         "fetches": dict(self.fetches),
                         "dispatch_parts": {
                             name.partition(".")[2]: v for name, v
                             in self._stages.parts().items()},
                         "queue_wait_s": self.queue_wait_s,
                         "queue_waits": self.queue_waits,
                         "ttft_s": self.ttft_s,
                         "ttfts": self.ttfts,
                         "first_decode_wait_s": self.first_decode_wait_s,
                         "first_decode_waits": self.first_decode_waits,
                         "preemptions": self.preemptions,
                         "batch_preemptions": self.batch_preemptions,
                         "completed_requests": self.completed_requests,
                         "tokens_generated": self.tokens_generated},
            "profile_armed": profile_armed,
            "last_release": last_release,
        }
        if self.wpool is not None:
            # the page store by layer group; the keys above read the FULL
            # group (what admission waits on and the gauges mean)
            ww, wr = self._window_walk
            out["pool"]["groups"] = {
                name: {"n_pages": grp.n_pages, "free_pages": grp.free_pages,
                       "page_nbytes": grp.page_nbytes,
                       "layers": grp.n_layers, "hbm_bytes": grp.hbm_bytes,
                       "walk_blocks": blocks, "walk_run_blocks": runs}
                for name, grp, blocks, runs in (
                    ("full", pool, self.walk_blocks - ww,
                     self.walk_run_blocks - wr),
                    ("window", self.wpool, ww, wr))}
        if self._spec is not None:
            out["spec"] = {"dispatches": self.spec_dispatches,
                           "fallbacks": self.spec_fallbacks,
                           "tokens_drafted": self.spec_tokens_drafted,
                           "tokens_accepted": self.spec_tokens_accepted,
                           "acceptance": round(self.spec_acceptance, 4),
                           "probes": self.spec_probes,
                           "probe_recoveries": self.spec_probe_recoveries}
        if self._moe_assignments is not None:
            first = self.model_spec.expert_first
            held = (self.model_spec.experts_held
                    or self.model_spec.ffn_experts)
            out["moe"] = {
                "expert_layers": list(self.model_spec.moe_layers),
                # the router's last columns that are identity experts (no
                # weights, no product): where they start and how many
                "zero_first": self.model_spec.ffn_experts,
                "zero_columns": self.model_spec.zero_experts,
                # cumulative (row, expert) assignments, [expert layer][expert]
                # over every column of the router
                "assignments": self._moe_assignments.tolist(),
                # the experts whose weights are here, and the assignments
                # that went to them, [expert layer]
                "first": first, "held": held,
                "assignments_here": self._moe_assignments[
                    :, first:first + held].sum(axis=1).tolist(),
                "decode_steps": self.moe_decode_steps,
                # experts held here with a row, summed over decode steps
                # and expert layers
                "experts_hit": self.moe_experts_hit,
                # the grouped products the step programs were traced at
                # and the kernel's tiles there (None: ``ragged_dot`` kept)
                "product": self.programs.expert_products(self.model_spec)}
        if self.model_spec is not None and self.model_spec.hc_mult:
            spec = self.model_spec
            out["mhc"] = {
                "streams": spec.hc_mult,
                # hyper-connected sublayers a forward: two a layer
                "sublayers": 2 * spec.n_layers,
                "sinkhorn_iters": spec.hc_sinkhorn_iters,
                # what a token row holds between sublayers
                "stream_bytes_per_row": spec.hc_mult * spec.d_model
                * np.dtype(self.plan.compute_dtype).itemsize,
                # token rows that went through the streams, cumulative
                "rows": {kind: self.lane_work[kind]["rows"]
                         for kind in ("round", "decode")}}
        if self.model_spec is not None and self.model_spec.cca_taps:
            out["cca"] = {
                "taps": list(self.model_spec.cca_taps),
                # what a lane keeps beside its pages, and a token in them
                "state_bytes_per_lane": self.state.bytes_per_lane,
                "kv_bytes_per_token": self.pool.bytes_per_token,
                # token rows that went through the layers, cumulative
                "rows": {kind: self.lane_work[kind]["rows"]
                         for kind in ("round", "decode")}}
        if self._sparse is not None:
            out["sparse"] = {"topk": self.model_spec.index_topk,
                             **{name: {kind: c[name]
                                       for kind, c in self._sparse.items()}
                                for name in self._sparse["decode"]}}
        if self._eva_stats is not None:
            e, spec = self._eva_stats, self.model_spec
            summary_rows = raw_rows = 0
            for ln in lanes:
                if ln["state"] != "idle":
                    summary_rows += ln["eva_done"] * spec.eva_summaries
                    raw_rows += ln["length"] - ln["eva_done"] * spec.eva_window
            out["eva"] = {"window": spec.eva_window, "chunk": spec.eva_chunk,
                          "compactions": dict(e["compactions"]),
                          "rows_compacted": e["rows_compacted"],
                          "pages_released": e["pages_released"],
                          "compact_s": e["compact_s"],
                          # what the live lanes hold right now
                          "summary_rows_live": summary_rows,
                          "raw_rows_live": raw_rows}
        if self._ssd is not None:
            # the chunked form's fill is rows / (chunks x chunk) of the
            # round half: a round's prompt tokens and a lane's segment
            # boundaries do not fall on whole chunks.  Every count is times
            # the state layers; a decode block runs the one-token form alone
            layers = len(self.model_spec.state_layers)
            out["ssd"] = {
                "chunk": self.model_spec.m2_chunk,
                "decode": {"chunks": 0, "passes": 0, "rows": 0,
                           "one_token_rows":
                               self.lane_work["decode"]["rows"] * layers},
                "round": {"chunks": self._ssd["chunks"] * layers,
                          "passes": self._ssd["passes"] * layers,
                          "rows": self.mixed_prompt_tokens * layers,
                          "one_token_rows":
                              self.mixed_decode_rows * layers}}
        if self.state is not None:
            # a slot is one lane's state in one layer of the store; a
            # dispatch HOLDS every slot and a pass of a lane through the
            # layers TOUCHES that lane's (what a rule that visits only the
            # lanes with a row reads and writes)
            layers = len(self.model_spec.state_layers)
            steps = {"decode": self.decode_block_steps,
                     "round": self.dispatch_kinds["mixed"]}
            out["state"] = {"kind": self.state.kind,
                            "lanes": self.state.lanes,
                            "bytes_per_lane": self.state.bytes_per_lane,
                            "hbm_bytes": self.state.hbm_bytes,
                            "zero_starts": self.zero_starts,
                            "rule": self.plan.state_rule,
                            "slots": {kind: {
                                "held": self.state.lanes * layers * n,
                                "touched":
                                    self.lane_work[kind]["passes"] * layers}
                                for kind, n in steps.items()}}
        pc = self.prefix_cache
        if pc is not None:
            out["prefix_cache"] = {"entries": len(pc), "hits": pc.hits,
                                   "misses": pc.misses,
                                   "host_promotions": pc.host_promotions}
        return out

    # -- scheduler ----------------------------------------------------------
    @staticmethod
    def _rank(req: _PagedRequest):
        """Scheduling rank: ``(class, priority)`` — every online request
        outranks every batch request (the offline lane sits strictly
        below online traffic at ANY priority); within a class, priority
        orders as before."""
        return (0 if req.batch else 1, req.priority)

    def _enqueue_locked(self, req: _PagedRequest,
                        front_of_class: bool) -> None:
        """Insert by rank (online before batch, higher priority first,
        FIFO within a class); ``front_of_class`` puts the request ahead
        of its equals (preempted victims resume before new same-priority
        arrivals)."""
        rank = self._rank(req)
        i = 0
        for i, q in enumerate(self._queue):
            if (self._rank(q) < rank
                    or (front_of_class and self._rank(q) == rank)):
                self._queue.insert(i, req)
                return
        self._queue.append(req)

    def _alloc_pages(self, n: int, after: int = 0) -> Optional[List[int]]:
        """``n`` pool pages in ascending runs, continuing ``after`` (the
        table's last page) where the pool can, all or nothing — evicting
        cold prefix-cache entries under pressure: live requests always
        outrank cached prefixes (with kv_offload the eviction DEMOTES the
        entry to the host tier instead of losing it)."""
        pages = self.pool.allocate_pages(n, after)
        while (pages is None and self.prefix_cache is not None
               and self.prefix_cache.evict_for_alloc()):
            pages = self.pool.allocate_pages(n, after)
        return pages

    def _alloc_page(self, after: int = 0) -> Optional[int]:
        """:meth:`_alloc_pages` of one."""
        pages = self._alloc_pages(1, after)
        return pages[0] if pages else None

    def _secure_pages(self, req: _PagedRequest, shared: List[int],
                      needed: int) -> bool:
        """The page table a prompt starts from: ``shared`` (the prefix
        cache's pages, first) and then private pages up to ``needed`` in
        all, in ONE grant.  The admission page goes back first, nothing
        written in it yet, and comes again as part of the grant, so that
        the private pages are one ascending run where the pool has one.
        False under page pressure, and then the request holds NOTHING: two
        starved prefills must not hold-and-wait each other."""
        private = max(needed - len(shared), len(req.pages))
        self.pool.release_pages(req.pages)
        pages = self._alloc_pages(private, shared[-1] if shared else 0)
        if pages is None:
            self.pool.release_pages(shared)
            req.pages = []
            return False
        req.pages = shared + pages
        return True

    # -- host KV tier (kv_offload) -------------------------------------------
    def _demote_prefix(self, digest: bytes, page: int) -> None:
        """PrefixCache.on_evict hook: spill the evicted page host-side."""
        self.kv_offload.demote(digest, page, self.pool.kv)

    def _promote_prefix(self, digest: bytes) -> Optional[int]:
        """PrefixCache.promote_fn hook: resurrect a demoted entry into a
        fresh pool page (plain allocate — promotion must not evict OTHER
        device entries and thrash the cache against itself)."""
        mgr = self.kv_offload
        if not mgr.has_prefix(digest):
            return None
        page = self.pool.allocate_page()
        if page is None:
            return None
        new_kv = mgr.promote(digest, page, self.pool.kv)
        if new_kv is None:
            self.pool.release_pages([page])
            return None
        self.pool.kv = new_kv
        return page

    # -- HBM economy (tpulab.hbm): the KV tenant --------------------------
    #: bound on how long a blocking grow request waits for a write-behind
    #: model eviction to land (only paid when every lane is starved —
    #: the scheduler had nothing else to do anyway)
    HBM_GROW_TIMEOUT_S = 0.5

    def _page_nbytes(self) -> int:
        return max(1, self.pool.page_nbytes)

    def _hbm_ladder_down(self, total: int) -> int:
        """Largest ladder size (base * 2^k) <= ``total`` (base floor)."""
        size = self._hbm_pool_base
        while size * 2 <= total:
            size *= 2
        return size

    def _hbm_reclaimable(self) -> int:
        """Non-mutating estimate of the KV bytes pressure could free:
        pages already contiguously free at the top of the store, plus
        idle prefix-cache pages, plus live-but-idle lane KV the host
        tier could absorb (demotion needs ``kv_offload`` — without the
        tier a preempted lane re-prefills, which frees pages but burns
        recompute, so it is not advertised as cheap headroom)."""
        pages = self.pool.shrinkable_pages()
        if self.prefix_cache is not None:
            pages += len(self.prefix_cache)
        if self.kv_offload is not None:
            with self._cv:
                lane_pages = sum(len(r.pages) for r in self._active
                                 if r is not None)
            pages = pages + min(lane_pages,
                                self.kv_offload.headroom_pages())
        return pages * self._page_nbytes()

    def _hbm_reclaim(self, nbytes: int) -> int:
        """Arbiter pressure hook (foreign thread): record the target and
        wake the scheduler — demotion/preemption/shrink run at the next
        tick boundary, where no dispatched block is in flight.  Returns
        the bytes this tenant expects to free (its progress promise)."""
        est = min(int(nbytes), self._hbm_reclaimable())
        if est <= 0:
            return 0
        with self._cv:
            self._hbm_reclaim_bytes = max(self._hbm_reclaim_bytes,
                                          int(nbytes))
            self._cv.notify()
        return est

    def _service_hbm_locked(self) -> None:
        """Serve an outstanding arbiter reclaim at the tick boundary:
        demote idle prefix-cache KV to the host tier, preempt
        live-but-idle lanes (their KV swaps out through the existing
        preemption path — the resumed stream is bit-exact), then shrink
        the page store's top and release the bytes to the ledger.  Only
        runs with no dispatched-ahead block in flight, so no in-flight
        decode page is ever victimized."""
        need = self._hbm_reclaim_bytes
        if not need or self.hbm is None or self._pending_block is not None:
            return
        from tpulab.hbm import KV_TENANT
        pn = self._page_nbytes()
        target = (need + pn - 1) // pn
        # snap the post-shrink total onto the size ladder (bounded
        # compiled shapes): free at least the target, landing on the
        # largest ladder size at or below what remains
        target = max(target, self.pool.n_pages
                     - self._hbm_ladder_down(
                         max(1, self.pool.n_pages - target)))
        # 1) idle KV first: cold prefix-cache entries demote for free
        while (self.pool.shrinkable_pages() < target
               and self.prefix_cache is not None
               and self.prefix_cache.evict_for_alloc()):
            pass
        # 2) live-but-idle lanes: preempt coldest-priority, least-progress
        # first — with kv_offload their KV demotes to the host tier and
        # the resume is recompute-free; without it the resume re-prefills
        # (the pre-arbiter preemption contract either way)
        while self.pool.shrinkable_pages() < target:
            victims = [(req.priority, -req.admit_seq, lane)
                       for lane, req in enumerate(self._active)
                       if req is not None]
            if not victims:
                break
            _, _, lane = min(victims)
            self._preempt_locked(lane)
            self.hbm_demotions += 1
        dropped = self.pool.shrink(target)
        self._hbm_reclaim_bytes = 0
        if dropped:
            self.hbm_shrinks += 1
            self.hbm.mirror_claim(KV_TENANT, "pool", self.pool.hbm_bytes)

    def _hbm_break_hoard_locked(self) -> None:
        """Preempt the most recently admitted lane when every lane is
        starved with nothing free — the hold-and-wait breaker for the
        elastic regime (see the _run call site).  The victim resumes
        exactly (preemption contract); progress resumes immediately."""
        if self.pool.free_pages > 0:
            return
        active = [(req.admit_seq, lane)
                  for lane, req in enumerate(self._active)
                  if req is not None and req.pages]
        if len(active) < 2:
            return  # one holder is not a hold-and-wait cycle
        _, lane = max(active)
        self._preempt_locked(lane)
        self.hbm_demotions += 1
        # the starvation streak stays up until a tick makes real
        # progress: admission is suppressed meanwhile (_admit_locked), so
        # the victim cannot re-admit and re-form the cycle before the
        # surviving holders finish

    def _hbm_maybe_grow(self, block: bool) -> bool:
        """Per-tick grow probe (scheduler thread, no locks held): when
        queued or starved requests want more pages than the pool holds,
        ask the arbiter for the bytes — the pressure protocol may evict
        a cold model to supply them.  ``block=True`` (every lane starved:
        nothing else to do) waits briefly for write-behind evictions to
        land; probes are free and retried next tick otherwise."""
        if self.hbm is None:
            return False
        with self._cv:
            if self._hbm_reclaim_bytes or self._pending_block is not None:
                return False  # being squeezed (or a block in flight)
            ps = self.page_size
            want = 0
            for req in self._queue[:self.lanes]:
                if req.kv_handle is not None:
                    want += req.kv_handle.n_pages + 1
                else:
                    t = len(req.pending_prompt) or (len(req.prompt)
                                                    + len(req.tokens_out))
                    want += (t + req.steps - len(req.tokens_out)
                             + ps - 1) // ps + 1
            for req in self._active:
                if req is None:
                    continue
                if req.pending_prompt:  # starved prefill / pending resume
                    want += max(0, (len(req.pending_prompt) + ps - 1) // ps
                                + 1 - len(req.pages))
                else:  # decoding: pages its remaining appends will write
                    need = (req.length + req.steps - len(req.tokens_out)
                            + ps - 1) // ps
                    want += max(0, need - len(req.pages))
            deficit = want - self.pool.free_pages
        if deficit <= 0:
            return False
        from tpulab.hbm import KV_TENANT
        pn = self._page_nbytes()
        # ask only for what the economy could plausibly supply (free
        # headroom + what pressure could evict) — an oversized request
        # would deny forever instead of growing incrementally — and snap
        # the new total onto the size ladder (bounded compiled shapes):
        # the smallest ladder size covering the demand we can afford,
        # else the largest affordable step toward it
        avail = (max(0, self.hbm.free_hbm_bytes)
                 + self.hbm.reclaimable_bytes(exclude=KV_TENANT))
        n = self.pool.n_pages
        affordable = n + avail // pn  # a rung may cost more than the
        #                               deficit — affordability is what
        #                               the economy could supply, period
        target = self._hbm_pool_base
        while target < n + deficit and target * 2 <= affordable:
            target *= 2
        pages = target - n
        if pages <= 0:
            return False  # static-budget degrade: queue on today's pool
        granted = self.hbm.request(
            KV_TENANT, ("pool", "grow"), pages * pn,
            timeout=self.HBM_GROW_TIMEOUT_S if block else 0.0,
            probe=not block)
        if not granted:
            return False
        with self._cv:
            if self._pending_block is None:
                self.pool.grow(pages)
                self.hbm_grows += 1
            # consolidate: fold the grant into the pool claim (mirror
            # first so the total never dips below the tracked bytes)
            self.hbm.mirror_claim(KV_TENANT, "pool", self.pool.hbm_bytes)
            self.hbm.release(KV_TENANT, ("pool", "grow"))
            self._cv.notify()
        return True

    def _admit_to_lane_locked(self, lane: int) -> bool:
        """Admit the queue head into a free lane (needs at least one page
        to start); False when the pool can't supply it."""
        page = self._alloc_page()
        if page is None:
            return False
        req = self._queue.pop(0)
        req.pages.append(page)
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        req.lane = lane
        self._active[lane] = req
        return True

    def _admit_locked(self) -> None:
        # elastic-regime hold-and-wait breaker (tpulab.hbm): while the
        # scheduler is in a starvation streak WITH live page-holders,
        # feed the pages freed by _hbm_break_hoard_locked to those
        # holders instead of re-admitting — the preempted victim
        # re-enters once decoding progresses.  With no holders at all
        # (e.g. right after an arbiter squeeze emptied every lane),
        # admission must proceed or nothing ever runs again.
        if not (self.hbm is not None and self._hbm_starved_passes >= 2
                and any(r is not None for r in self._active)):
            for lane in range(self.lanes):
                if self._active[lane] is None and self._queue:
                    if not self._admit_to_lane_locked(lane):
                        break
        # preemption: while the queue head strictly outranks the weakest
        # active request (rank = (class, priority): BATCH lanes are the
        # first victims — any online arrival evicts batch work before
        # touching another online lane; within a class the priority
        # tie-break stays most-recently-admitted falls first — least
        # progress lost), evict it and admit the head.  Zero-page lanes
        # (page-starved prefills) are skipped: evicting them frees
        # nothing and they already yield every tick.
        while self._queue:
            head = self._queue[0]
            head_rank = self._rank(head)
            # a victim only helps if releasing it can actually free a page:
            # skip lanes whose every page is prefix-cache-shared
            # (refcount > 1) — preempting them loses decode progress for
            # zero freed pages
            victims = [(self._rank(req) + (-req.admit_seq, lane))
                       for lane, req in enumerate(self._active)
                       if req is not None and self._rank(req) < head_rank
                       and any(self.pool.refcount(p) == 1
                               for p in req.pages)]
            if not victims:
                return
            lane = min(victims)[-1]
            self._preempt_locked(lane)
            if not self._admit_to_lane_locked(lane):
                # Defensive: the victim filter above requires at least one
                # refcount==1 page, so every preemption frees >=1 page and
                # a one-page admit succeeds under the current filter.  Kept
                # as a guard for future filter changes (e.g. admitting
                # multi-page heads) — the head retries next scheduling pass.
                return

    def _preempt_locked(self, lane: int) -> None:
        """Evict the lane's request: free its pages now, re-queue it for an
        exact-token resume (re-prefill of prompt+generated; no sampling
        PRNG draws are consumed on resume, so seeded sequences are
        unchanged by preemption).  With ``kv_offload`` the lane's live KV
        pages are first snapshotted device->host (async write-behind —
        only the gather dispatch happens here); the resume then swaps
        them back in with zero prefill dispatches, and the re-prefill
        below becomes the FALLBACK for a failed/dropped swap."""
        req = self._active[lane]
        self._fl_pages(req)
        if req.fl is not None:
            req.fl["preempts"] += 1
        # a mid-prompt ragged lane (length > 0 with chunks still pending)
        # is never snapshotted: its partial-prompt KV does not match the
        # resume length contract below — the resume re-prefills exactly
        if (self.kv_offload is not None and req.length > 0
                and not req.pending_prompt):
            t_sw0 = _time.perf_counter()
            needed = (req.length + self.page_size - 1) // self.page_size
            req.kv_handle = self.kv_offload.swap_out(
                req.pages[:needed], req.length, self.pool.kv)
            if req.kv_handle is not None:
                if req.fl is not None:
                    req.fl["swap_outs"] += 1
                self._span("swap_out", lane, t_sw0,
                           _time.perf_counter() - t_sw0, req,
                           pages=needed, tokens=req.length)
        self.pool.release_pages(req.pages)
        req.pages = []
        if req.wpages:
            self.wpool.release_pages(req.wpages)
        req.wpages, req.wfirst = [], 0
        # the draft table is never snapshotted: it is cheap to regenerate
        # (one draft forward at resume), so its pages go home NOW and the
        # resume's warm-up rebuilds it exactly
        if req.draft_pages:
            self.pool.release_pages(req.draft_pages)
            req.draft_pages = []
        req.draft_len = 0
        if req.tokens_out:
            # feed everything but the last emitted token; the resume
            # prefill's logits are discarded (that pick already happened)
            req.pending_prompt = (list(req.prompt)
                                  + list(req.tokens_out[:-1]))
            req.resumed = True
        else:
            req.pending_prompt = list(req.prompt)
        req.length = 0
        req.eva_done = 0
        req.pf_started = False   # the resume re-secures its pages
        self._active[lane] = None
        self._enqueue_locked(req, front_of_class=True)
        self.preemptions += 1
        if req.batch:
            self.batch_preemptions += 1

    def _run(self) -> None:
        import jax.numpy as jnp
        st = self._stages
        while True:
            with stage(st, "admit"), self._cv:
                while (not self._shutdown and not self._queue
                       and not any(self._active)
                       and not self._hbm_reclaim_bytes):
                    with stage(st, "idle"):
                        self._cv.wait()
                if self._shutdown and not self._queue and not any(self._active):
                    self._profile_step(done=True)  # close an open capture
                    return
                # HBM arbiter pressure: serve an outstanding reclaim at
                # the tick boundary (no dispatched block is in flight
                # here — dispatch-ahead is suppressed while a reclaim is
                # pending, so in-flight decode pages are never victims)
                self._service_hbm_locked()
                # cancellation + deadline sweep: unconditional, so cancels
                # and expiries land even when no lane can make progress
                # (page-starved prefills).  Expired requests free their
                # lane and pages HERE — before the next step runs
                swept = []
                expired = []
                now = _time.monotonic()
                for lane, req in enumerate(self._active):
                    if req is None:
                        continue
                    if req.cancelled:
                        self._release_lane_locked(lane, req)
                        swept.append(req)
                    elif req.deadline is not None and now >= req.deadline:
                        self._release_lane_locked(lane, req)
                        expired.append(req)
                if self._queue:  # queued requests expire in place
                    still = []
                    for req in self._queue:
                        if (req.deadline is not None
                                and now >= req.deadline):
                            self._requests.pop(req.future, None)
                            self._discard_handle(req)
                            expired.append(req)
                        else:
                            still.append(req)
                    self._queue[:] = still
                self._admit_locked()
                snapshot = list(self._active)
            self._profile_step()  # debugz on-demand capture bookkeeping
            for req in swept:
                self._flight_complete(req, "CANCELLED")
                if not req.future.done():
                    req.future.cancel() or req.future.set_exception(
                        RuntimeError("generation cancelled"))
            for req in expired:
                if self.metrics is not None:
                    self.metrics.note_deadline_expired()
                self._flight_complete(req, "DEADLINE_EXCEEDED")
                if not req.future.done():
                    req.future.set_exception(DeadlineExceeded(
                        "generation deadline exceeded "
                        f"({len(req.tokens_out)}/{req.steps} tokens)"))
            try:
                if self.plan.eva_window:
                    self._eva_compact()
                # pending prompts and decode lanes advance together in ONE
                # fused mixed round (the head of a chain here; with a
                # dispatch in flight the chain plans its own rounds as
                # _tick consumes it)
                prefilled = self._ragged_round(snapshot, jnp)
                if prefilled:
                    # the round's consume released what finished in it (a
                    # steps == 1 request) and admitted behind it: decode
                    # goes on with the lanes as they stand now
                    with stage(st, "admit"), self._cv:
                        self._admit_locked()
                        snapshot = list(self._active)
                if self.plan.eva_window:
                    self._eva_compact()     # a window the round finished
                progressed = self._tick(snapshot, jnp) or prefilled
                if self.hbm is not None:
                    # KV-burst side of the economy: queued/starved demand
                    # asks the arbiter for pool bytes (a cold model may be
                    # evicted to supply them); a cheap probe per tick,
                    # blocking only when every lane is starved anyway
                    self._hbm_maybe_grow(block=not progressed)
                if not progressed:
                    if self.hbm is not None:
                        # elastic-regime hold-and-wait breaker: lanes are
                        # sized for the GROWN pool, so a denied grow can
                        # strand N partial page-holders where the static
                        # world (lanes sized to the fixed pool) never
                        # could.  After two fully-starved passes with
                        # nothing free, preempt the newest lane (exact
                        # resume) so the eldest can finish — degraded
                        # throughput, never a livelock.
                        self._hbm_starved_passes += 1
                        if self._hbm_starved_passes >= 2:
                            with self._cv:
                                self._hbm_break_hoard_locked()
                    # every lane starved (pool pressure): back off instead
                    # of hot-spinning until pages free up
                    with stage(st, "idle"), self._cv:
                        self._cv.wait(timeout=0.01)
                else:
                    self._hbm_starved_passes = 0
            except Exception as e:  # noqa: BLE001 - fail active requests
                # a dispatched-ahead block died with the pool: its device
                # arrays and lane mapping are meaningless after recovery
                self._pending_block = None
                with self._cv:
                    for lane, req in enumerate(self._active):
                        if req is not None:
                            if not req.future.done():
                                self._flight_complete(req, "INTERNAL")
                                req.future.set_exception(e)
                            self._requests.pop(req.future, None)
                            self._active[lane] = None
                # donated pools may be gone after a failed step — rebuild
                if self.prefix_cache is not None:
                    self.prefix_cache.drop_all()  # entries died with the pool
                self.pool.reset()
                if self.wpool is not None:
                    self.wpool.reset()
                if self.state is not None:
                    self.state.reset()

    #: published fabric snapshots kept addressable (digest -> handle);
    #: beyond this the oldest export is forgotten — its store entries
    #: removed — so the fabric can never squat the whole host tier
    FAB_PUBLISH_CAP = 32

    def _publishes(self, req: _PagedRequest, was_resumed: bool) -> bool:
        """Whether the round that ends ``req``'s prompt exports it to the
        fabric: a FIRST prefill (a resume's pages hold generated tokens
        too, and its pick was made before) that no prefill replica's
        hand-off takes at release.  Never without ``kv_publish``."""
        return (self.kv_publish and not was_resumed
                and req.export_digest is None)

    def _fab_publish(self, req: _PagedRequest, last_logits) -> None:
        """Export a finished first prefill to the fleet KV fabric
        (tpulab.kvfabric): the prompt's pages snapshot to the host tier
        under ``("fab", digest)`` through the same write-behind swap_out
        the preemption path uses (gather dispatched HERE, behind the round
        that ended the prompt and before any decode write into the tail
        page: :meth:`_chain_block` enqueues nothing behind such a round,
        so dispatch ordering makes the snapshot prompt-only), and the
        last-position logits row lands
        beside it under ``("fablog", digest)`` so a fetcher picks the
        first token under its OWN sampling seed.  Best-effort end to
        end: a degraded swap, a budget-refused put or a mid-flight
        eviction all surface as an honest FetchKV NOT_FOUND — never a
        wrong answer."""
        from tpulab.disagg.wire import prompt_digest
        digest, t = prompt_digest(req.prompt), len(req.prompt)
        with self._fab_lock:
            if digest in self._fab_handles:
                self._fab_handles.move_to_end(digest)
                return
        n_pages = (t + self.page_size - 1) // self.page_size
        handle = self.kv_offload.swap_out(
            req.pages[:n_pages], t, self.pool.kv, key=("fab", digest))
        if handle is None:
            return
        if not self.kv_offload.store.put(
                ("fablog", digest),
                self._fetch(last_logits).astype(np.float32).reshape(-1)):
            self.kv_offload.discard(handle)
            return
        self.kv_publishes += 1
        with self._fab_lock:
            self._fab_handles[digest] = handle
            self._fab_handles.move_to_end(digest)
            while len(self._fab_handles) > self.FAB_PUBLISH_CAP:
                old_dig, old_h = self._fab_handles.popitem(last=False)
                self.kv_offload.discard(old_h)
                self.kv_offload.store.remove(("fablog", old_dig))

    def fab_handle(self, digest: bytes):
        """The published fabric snapshot for ``digest`` (a resident or
        still-in-flight :class:`~tpulab.kvcache.offload.SwapHandle`), or
        None — the FetchKV server's lookup.  Thread-safe: the RPC thread
        reads while the scheduler publishes/evicts.  A hit bumps the
        publish-registry LRU (fabric-popular digests stay addressable)
        WITHOUT touching the host store's own recency — the store read
        goes through ``peek``."""
        with self._fab_lock:
            h = self._fab_handles.get(digest)
            if h is not None:
                self._fab_handles.move_to_end(digest)
            return h

    def _try_swap_in(self, req: _PagedRequest, lane: int) -> Optional[bool]:
        """Restore a preempted lane's host-tier KV snapshot into freshly
        allocated pages: True = restored (no prompt row is computed), False
        = page-starved (the handle kept, retry next pass), None = the swap
        degraded (the handle consumed: the caller re-prefills exactly).
        The resume length (prompt + generated - 1, what ``pending_prompt``
        holds) is by construction the positions the snapshot covers."""
        handle, t = req.kv_handle, len(req.pending_prompt)
        needed = handle.n_pages
        if not self._secure_pages(req, [], needed):
            # page pressure: nothing held (no hold-and-wait), the handle
            # KEPT — the snapshot outlives retries
            return False
        t0 = _time.perf_counter()
        new_kv = self.kv_offload.restore(handle, req.pages[:needed],
                                         self.pool.kv)
        req.kv_handle = None
        if new_kv is None:
            # degraded swap: hand the pages back and run the normal
            # re-prefill (which re-does prefix lookup and its own page
            # accounting from a clean slate)
            self.pool.release_pages(req.pages)
            req.pages = []
            return None
        self.pool.kv = new_kv
        req.length = t
        req.pending_prompt = []
        req.resumed = False  # the first-token pick happened pre-preemption
        now = _time.perf_counter()
        if req.fl is not None:
            req.fl["swap_ins"] += 1
        self._fl_pages(req)
        self._span("swap_in", lane, t0, now - t0, req,
                   pages=needed, tokens=t)
        req.chunk_t0 = now        # decode chunks restart here
        req.chunk_start = len(req.tokens_out)
        return True

    # -- mixed prefill+decode rounds -----------------------------------------
    #: max prefill tokens one mixed round carries IN TOTAL (the ceiling
    #: of the pow2 bucket the mixed program is keyed by): lanes that
    #: prefill at once share it, longer prompts take multiple rounds,
    #: decode lanes never stall behind them.  A round reads every weight
    #: once whatever its rows, so a prompt costs a weight pass, a turn of
    #: the host and a gap in the decode chain every this many tokens.  On
    #: the class it is the ceiling, as wide as the rows kernels hold a
    #: segment today (a whole query and its carry in VMEM: 1,024 rows do
    #: not fit at a published model's heads).  An ENGINE's attribute of
    #: this name is what its constructor derived from its shapes: the
    #: widest power of two under the ceiling that ``max_len`` holds twice
    #: and that its kernels' geometry rule admits
    #: (``debug_state()["dispatch"]["round_budget_why"]`` says what
    #: refused the next wider); ``prefill_chunk`` lowers it
    RAGGED_CHUNK_CAP = 512

    @property
    def _round_budget(self) -> int:
        """Prefill tokens one mixed round may carry, all lanes together
        (the token budget of chunked prefill): the engine's
        ``RAGGED_CHUNK_CAP``, or ``prefill_chunk`` where that is less."""
        return min(self.prefill_chunk or self.RAGGED_CHUNK_CAP,
                   self.RAGGED_CHUNK_CAP)

    def _ragged_prefill_start(self, req: _PagedRequest, lane: int) -> bool:
        """Host half of a prefill: prefix-cache lookup + secure EVERY page
        the full prompt needs, all of them or none (a lane that held some
        while it waited for the rest could starve another that does the
        same: no hold-and-wait), then mark the lane chunk-ready.
        True = segments may build; False = page-starved (retry later)."""
        prompt = np.asarray(req.pending_prompt, np.int32)
        t = len(prompt)
        shared: List[int] = []
        digests: List[bytes] = []
        if self.prefix_cache is not None:
            shared, digests = self.prefix_cache.lookup(prompt,
                                                       self.page_size)
        # with EVA windows: the most ROWS the prompt holds on its way in
        needed = (self._peak_rows(req, t) + self.page_size
                  - 1) // self.page_size
        if not self._secure_pages(req, shared, needed):
            return False
        # shared prefix positions are already resident: chunks cover
        # only the tail (the last prompt token is never served shared)
        req.pf_digests = digests
        req.pf_shared = len(shared)
        req.length = len(shared) * self.page_size
        del req.pending_prompt[:req.length]
        req.pf_started = True
        req.pf_t0 = _time.perf_counter()
        if req.t_prefill0 is None:
            req.t_prefill0 = req.pf_t0
            self._span("queue_wait", lane, req.t_submit,
                       req.pf_t0 - req.t_submit, req)
            self.queue_wait_s += req.pf_t0 - req.t_submit
            self.queue_waits += 1
            if self.metrics is not None:
                self.metrics.observe_queue_wait(req.pf_t0 - req.t_submit)
        # chaos: the prefill fault site, one trip a prefill start: an error
        # here rides the scheduler's recovery path (fail actives + pool
        # reset), a delay is a slow prefill under deadline pressure
        chaos.trip("engine.prefill")
        return True

    def _ragged_round(self, snapshot, jnp) -> bool:
        """Head a chain with a fused ragged mixed round (the unified
        dispatch plan): with nothing in flight and a prompt waiting,
        prefilling lanes advance by a prompt chunk and every decoding lane
        by one token, all through ONE ``paged_mixed_step`` dispatch over
        per-lane ``(q_len, kv_len)`` segments, packed by token
        (:meth:`_dispatch_round`), fresh in every lane; its consume
        (:meth:`_consume_round`) enqueues the dispatch behind it before it
        fetches this one.  With a dispatch in flight this is a no-op: the
        chain plans its own rounds (:meth:`_chain_block`: while a prompt
        waits every dispatch is a round, and every round carries every
        decoding lane from its predecessor's device carry).  With no
        pending prompts it is a no-op too and the K-block decode path owns
        the tick.  Returns True when any lane made progress."""
        if self._pending_block is not None:
            return False
        st = self._stages
        with stage(st, "plan"):
            segs, progressed = self._round_segments(snapshot)
            if not segs:
                return progressed
            decoding = [(lane, req) for lane, req in enumerate(snapshot)
                        if req is not None and not req.pending_prompt
                        and not req.cancelled and req.tokens_out]
            rows = self._round_rows(decoding)
        with stage(st, "dispatch"):
            stash = self._dispatch_round(segs, rows)
        self._consume_round(stash, jnp)
        return True

    def _round_segments(self, snapshot):
        """The lanes of ``snapshot`` whose prompt has tokens a round can
        carry, ``[(lane, req), ...]``, their pages secured (a snapshot of a
        preempted lane swapped back in instead; a page-starved lane waits
        a pass), and whether a swap-in made progress."""
        progressed = False
        segs: List = []
        for lane, req in enumerate(snapshot):
            if req is None or not req.pending_prompt or req.cancelled:
                continue
            if req.kv_handle is not None:
                swapped = self._try_swap_in(req, lane)
                if swapped is True:
                    progressed = True
                    continue
                if swapped is False:
                    continue         # page-starved: snapshot kept
            if not req.pf_started and not self._ragged_prefill_start(
                    req, lane):
                continue             # page-starved: retry next pass
            segs.append((lane, req))
        return segs, progressed

    def _round_rows(self, decoding, lag=None):
        """The lanes of ``decoding`` a round can carry as decode rows: those
        whose next row's page is reserved (a one-step block's reservation,
        :meth:`_reserve_block_pages`, ``lag`` as there; a starved lane is
        left out)."""
        return [(lane, req) for lane, req, _new
                in self._reserve_block_pages(decoding, 1, lag)[1]]

    @staticmethod
    def _host_sampled(req: _PagedRequest) -> bool:
        """``top_k`` / ``top_p`` / host-PRNG temperature: the pick needs
        the logits row on the host."""
        return req.sampling.temperature > 0.0 and not req.sampling.device

    def _dispatch_round(self, segs, decode_parts, chain=None):
        """Issue one mixed round (async, no host sync), inside the caller's
        ``dispatch`` stage: ``segs`` advance by a prompt chunk,
        ``decode_parts`` by one token.  The round's prompt tokens never
        exceed ``_round_budget`` in total: lanes that prefill at once share
        it, the oldest admission first; what is left of a chunk, or a lane
        the budget did not reach, waits a round (the oldest lane always
        advances, so none starves).  The program is keyed by
        :func:`round_width` of the tokens carried, so the budget also
        bounds the programs: a power of two each from 2 up to the budget
        (nine at 512), each reached by a single prompt, with or without a
        predecessor.

        ``chain`` is the dispatch in flight this round goes behind (a
        decode block or a round, un-fetched): the decode rows are then not
        ``fresh`` and take token, length, budget and liveness from its
        device carry; a chain's first round sends all of it beside
        ``_no_carry``, exactly as a chain's first block does.

        What the round changes on the host that needs no result is
        committed HERE: a chunk lane's ``length`` and ``pending_prompt``,
        and which lanes finish their prompt (their first token is a row of
        ``out``).  Tokens, logprobs, TTFT and the expert counters wait for
        the fetch (:meth:`_consume_round`)."""
        st = self._stages
        with part(st, "dispatch.arrays"):
            left = self._round_budget
            chunks: Dict[int, int] = {}
            for lane, req in sorted(segs, key=lambda s: s[1].admit_seq):
                # a chunk ends where its lane's window does: the
                # compaction comes before the next row is written
                chunks[lane] = min(len(req.pending_prompt), left,
                                   self._boundary(req) - req.length)
                left -= chunks[lane]
            segs = [(lane, req) for lane, req in segs if chunks[lane]]
            if self.state is not None:
                self.zero_starts += sum(req.length == 0 for _, req in segs)
            b = self.lanes
            toks, row_lane, row_off, q_lens = pack_round(
                b, {lane: req.pending_prompt[:chunks[lane]]
                    for lane, req in segs},
                {lane: 0 if chain else req.tokens_out[-1]
                 for lane, req in decode_parts})
            if self.wpool is not None:
                for lane, req in segs:
                    self._window_pages(req, req.length,
                                       req.length + chunks[lane] - 1)
            tables = self._lane_tables(segs + decode_parts)
            kv_lens = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            seeds = np.zeros((b, 2), np.uint32)
            fresh = np.ones((b,), bool)
            rem = np.zeros((b,), np.int32)
            stops = np.full((b, ROUND_STOPS), -1, np.int32)
            host_lanes: List[int] = []
            lane_reqs: Dict[int, _PagedRequest] = {}
            for lane, req in segs + decode_parts:
                lane_reqs[lane] = req
                kv_lens[lane] = req.length + q_lens[lane]
                if req.pending_prompt and q_lens[lane] < len(
                        req.pending_prompt):
                    continue        # mid-prompt: no pick, no budget
                # the tokens it still wants, this round's included
                rem[lane] = req.steps - len(req.tokens_out)
                ids = sorted(req.stop_tokens)[:ROUND_STOPS]
                stops[lane, :len(ids)] = ids
                # a prompt's pick counts only off its final chunk, where it
                # IS the first token (a resumed request made it before)
                sp = req.sampling
                if sp.temperature > 0.0 and not (req.pending_prompt
                                                 and req.resumed):
                    if sp.device:
                        temps[lane] = sp.temperature
                        seeds[lane] = (sp.seed & 0xFFFFFFFF,
                                       (sp.seed >> 32) & 0xFFFFFFFF)
                    else:
                        host_lanes.append(lane)
            if chain is not None:
                fresh[[lane for lane, _ in decode_parts]] = False
            buf = pack_words(self.programs.fields["round"], dict(
                tables, q_lens=q_lens, kv_lens=kv_lens,
                temps=temps, seeds=seeds, fresh=fresh, rem=rem,
                stops=stops, rows=np.stack([toks, row_lane, row_off])))
        if decode_parts:
            # decode lanes advance one tick this round — same fault site
            chaos.trip("engine.step")
        t0 = _time.perf_counter()
        with part(st, "dispatch.put"):
            packed = self._put(buf)
        with part(st, "dispatch.call"):
            (out, last_dev, len_f, tok_f, live_f, rem_f,
             self._kv_state) = self.programs.mixed(
                self.params, self._kv_state, packed,
                chain["carry"] if chain else self._no_carry)
            # the program is on the device's queue: the turn ends here,
            # the results' copy to the host starts behind it
            ticket = st.launched()
            out.copy_to_host_async()
        carried = self._round_budget - left
        st.note(program="paged_mixed_step", k=1, lanes=len(lane_reqs),
                rows=len(toks), prompt_tokens=carried,
                decode_rows=len(decode_parts), ahead=int(chain is not None))
        self.decode_dispatches += 1
        self._note_dispatch("mixed")
        self.mixed_rows += len(toks)
        self.mixed_tokens += int(q_lens.sum())
        self.mixed_prompt_tokens += carried
        self.budget_rounds += left == 0
        self.mixed_attn_rows += ((len(toks) - b) * len(segs)
                                 + len(decode_parts))
        self.round_chunk_lanes += len(segs)
        if self._ssd is not None:
            q = min(self.model_spec.m2_chunk, len(toks) - b)
            self._ssd["chunks"] += (len(toks) - b) // q
            row = 0
            for lane, _req in segs:     # packed in this order, end to end
                self._ssd["passes"] += ((row + chunks[lane] - 1) // q
                                        - row // q + 1)
                row += chunks[lane]
        self.mixed_decode_rows += len(decode_parts)
        self._note_walk(req for _, req in decode_parts)
        if chain is not None:
            self.ahead_rounds += 1
            self.rounds_after_round += chain["kind"] == "round"
        firsts: List = []      # (lane, req, its pick was made before)
        with self._cv:
            for lane, req in segs:
                c = chunks[lane]
                self._note_rows("round", req.length, c)
                req.length += c
                del req.pending_prompt[:c]
                self._fl_pages(req)
                if not req.pending_prompt:
                    firsts.append((lane, req, req.resumed))
                    req.resumed = False
                    req.pf_started = False
        # once this round is done these lanes decode: its decode rows, a
        # position and a token behind on the host until its fetch, and the
        # lanes whose prompt ended here, a token behind
        after = dict(decode_parts)
        after.update((lane, req) for lane, req, _ in firsts)
        lag = {lane: (1, 1) for lane, _ in decode_parts}
        lag.update((lane, (0, 1)) for lane, _req, _ in firsts)
        return {"kind": "round", "k": 1, "lane_reqs": lane_reqs,
                "out": out, "last": last_dev,
                "carry": (len_f, tok_f, live_f, rem_f), "host": None,
                "firsts": firsts, "decodes": decode_parts,
                "host_lanes": host_lanes, "next": after, "ahead": lag,
                "t0": t0, "ticket": ticket}

    def _consume_round(self, stash, jnp) -> bool:
        """Fetch a dispatched round and commit what needed its result:
        the first token of each lane whose prompt ended in it, a token for
        each decode row.  The dispatch behind it is enqueued first where
        :meth:`_chain_block` allows (a round while a prompt still waits,
        else a K-block), so the device computes it while the host fetches,
        commits and emits this one.  A lane released since the dispatch
        (cancel, deadline sweep, preemption, a stop token in the dispatch
        before, which left its row here dead) has its pick discarded."""
        st = self._stages
        self._pending_block, why = self._chain_block(stash, jnp, ahead=1)
        if why is not None:
            self.chain_breaks[why] += 1
        host_lanes, lane_reqs = stash["host_lanes"], stash["lane_reqs"]
        with stage(st, "fetch"):
            res = self._results(stash["out"])
            next_tokens = res["tokens"].copy()
            logprobs_arr = res["logprobs"].copy()
            self.decode_host_syncs += 1
            self._note_moe(res.get("moe"), decode=False)
            if host_lanes:
                # fetch ONLY the host-sampled rows (same shape discipline —
                # and PRNG rule — as _tick_single)
                rows = self._fetch(stash["last"][
                    self._put(np.asarray(host_lanes, np.int32))])
                self.decode_host_syncs += 1
                for i, lane in enumerate(host_lanes):
                    req = lane_reqs[lane]
                    next_tokens[lane] = req.sampling.pick(rows[i])
                    if req.want_logprobs:
                        row = rows[i].astype(np.float32)
                        row = row - row.max()
                        logprobs_arr[lane] = float(
                            row[next_tokens[lane]]
                            - np.log(np.exp(row).sum()))
            st.landed(stash["ticket"],
                      why if why in self.TURN_CAUSES else "round",
                      lanes=len(lane_reqs))
        for lane, req, was_resumed in stash["firsts"]:
            # a first prompt that ended here is exported as this round
            # left it (no dispatch stands behind the round: _chain_block);
            # the scheduler's thread alone changes ``_active`` and a lane's
            # pages, so they are read as the commit below will find them
            if (self._publishes(req, was_resumed)
                    and self._active[lane] is req and not req.cancelled):
                self._fab_publish(req, stash["last"][lane])
        now = _time.perf_counter()
        # a round enqueued ahead started when its predecessor ended, which
        # the host saw as the previous fetch's return
        step_s = now - max(stash["t0"], self._block_fetched_t)
        self._block_fetched_t = now
        self._step_ewma_s = (0.8 * self._step_ewma_s + 0.2 * step_s
                             if self._step_ewma_s else step_s)
        emits: List = []
        completed: List = []
        with stage(st, "commit"), self._cv:
            for lane, req, was_resumed in stash["firsts"]:
                if (self._active[lane] is not req or req.cancelled
                        or req.pending_prompt):
                    continue
                # a resumed request's pick happened before preemption / on
                # the prefill replica: this round's (stateless) sample is
                # discarded, the lane just continues decoding
                if not was_resumed:
                    tok = int(next_tokens[lane])
                    req.tokens_out.append(tok)
                    self.tokens_generated += 1
                    lp = None
                    if req.want_logprobs:
                        lp = float(logprobs_arr[lane])
                        req.logprobs_out.append(lp)
                    emits.append((req, tok, len(req.tokens_out) - 1, lp))
                self._span("prefill", lane, req.pf_t0, now - req.pf_t0,
                           req, prompt_tokens=req.length,
                           cached_pages=req.pf_shared)
                req.chunk_t0 = now
                req.chunk_start = len(req.tokens_out)
                if not was_resumed:
                    req.t_first = now
                    req.t_last = now
                    self.ttft_s += now - req.t_submit
                    self.ttfts += 1
                    if self.metrics is not None:
                        self.metrics.observe_ttft(now - req.t_submit)
                    if now > req.pf_t0:
                        # rolling prefill throughput: what the fabric's
                        # cost gate takes a recompute of the prompt to cost
                        inst = len(req.prompt) / (now - req.pf_t0)
                        self.prefill_ewma_tok_s = (
                            inst if self.prefill_ewma_tok_s == 0.0 else
                            0.7 * self.prefill_ewma_tok_s + 0.3 * inst)
                    if self.prefix_cache is not None:
                        self.prefix_cache.count_lookup(req.pf_shared,
                                                       len(req.pf_digests))
                        self.prefix_cache.insert(
                            req.pf_digests, req.pages[:len(req.pf_digests)])
                if req.finished():       # a steps == 1 request
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            for lane, req in stash["decodes"]:
                if (self._active[lane] is not req or req.cancelled
                        or req.pending_prompt):
                    continue
                self._probe_countdown_locked(req)
                self._note_second_token(req, now)
                self._note_rows("round", req.length, 1)
                req.length += 1
                tok = int(next_tokens[lane])
                req.tokens_out.append(tok)
                self.tokens_generated += 1
                dt = (now - req.t_last) if req.t_last is not None else None
                if self.metrics is not None and dt is not None:
                    self.metrics.observe_itl(dt)
                self._fl_block(req, 1, 1, dt)
                req.t_last = now
                lp = None
                if req.want_logprobs:
                    lp = float(logprobs_arr[lane])
                    req.logprobs_out.append(lp)
                emits.append((req, tok, len(req.tokens_out) - 1, lp))
                done = req.finished()
                if (done or len(req.tokens_out) - req.chunk_start
                        >= self.TRACE_DECODE_CHUNK):
                    self._flush_decode_chunk(req, lane, now)
                if done:
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        self._deliver(emits, completed)
        return True

    def _discard_handle(self, req: _PagedRequest) -> None:
        """Drop a never-to-be-restored snapshot (cancel/expiry while
        queued) so it stops holding host-tier budget."""
        if req.kv_handle is not None:
            if self.kv_offload is not None:
                self.kv_offload.discard(req.kv_handle)
            req.kv_handle = None

    @staticmethod
    def _emit(req: _PagedRequest, token: int, index: int,
              logprob: Optional[float] = None) -> None:
        """Explicit hook contract: ``on_token(tok, i)`` normally;
        ``on_token(tok, i, logprob)`` iff the request asked for
        ``logprobs=True`` (no signature sniffing — a 3-arg call on a
        2-arg hook with ``logprobs=True`` is a caller bug and raises)."""
        if req.on_token is not None:
            try:
                if req.want_logprobs:
                    req.on_token(token, index, logprob)
                else:
                    req.on_token(token, index)
            except Exception:  # pragma: no cover - consumer hook
                import logging
                logging.getLogger("tpulab.engine").exception(
                    "on_token hook failed")

    def _deliver(self, emits, completed) -> None:
        """User callbacks and future resolution, OUTSIDE the scheduler
        lock: a slow consumer must not head-of-line-block other lanes."""
        with stage(self._stages, "emit"):
            for req, tok, i, lp in emits:
                self._emit(req, tok, i, lp)
            for req in completed:
                if not req.future.done():
                    self._flight_complete(req)
                    req.future.set_result(self._result_of(req))
                    self.completed_requests += 1
                    self._note_complete(req)

    def _note_second_token(self, req: _PagedRequest, now: float) -> None:
        """Called as a request's second token is committed: its wait
        since the first is what a newly admitted lane waits for the
        running chain (dispatch-ahead) before it gets a decode step."""
        if len(req.tokens_out) == 1 and req.t_first is not None:
            self.first_decode_wait_s += now - req.t_first
            self.first_decode_waits += 1

    def _note_moe(self, moe, decode: bool) -> None:
        """Add a dispatch's expert counters (``(n_moe, E + 2)`` out of the
        step program's results, or None for a model without expert
        layers) to the totals: they came with the dispatch's tokens, in
        the same fetch.  Mixed rounds count assignments only; decode
        dispatches also the steps that had a live lane and the experts
        those steps hit."""
        if moe is None:
            return
        stats = moe.astype(np.int64)
        n = self._moe_assignments.shape[1]
        self._moe_assignments += stats[:, :n]
        if decode:
            self.moe_experts_hit += int(stats[:, n].sum())
            self.moe_decode_steps += int(stats[0, n + 1])

    def _note_dispatch(self, kind: str) -> None:
        """Dispatch-kind accounting (the three descriptor kinds);
        ``ragged_dispatches`` counts the ragged kernel family —
        every mixed round, plus decode/verify dispatches whose attention
        ran the pallas ragged kernel."""
        self.dispatch_kinds[kind] += 1
        if kind == "mixed" or self.use_kernel:
            self.ragged_dispatches += 1

    # -- fused decode dispatch ----------------------------------------------
    def _tight_slack_s(self) -> float:
        """Deadline slack below which a lane counts as *tight* (adaptive K
        drops to <=2): roughly two max-size blocks of measured decode
        time, clamped to a sane band while the EWMA warms up."""
        est = self._step_ewma_s or 0.005
        return min(1.0, max(0.05, 2.0 * self.decode_block * est))

    def _pick_block_k(self, decode_lanes, lag=None) -> int:
        """Adaptive fused-decode block size for this dispatch.  ``lag``
        (lane -> (positions, tokens)) is what a dispatch still in flight
        gives a lane that its committed length and step budget do not show
        yet (see :meth:`_chain_block`).

        - any host-sampled (``top_k``/``top_p``) lane -> 1: its per-token
          pick needs the logits row on host every tick;
        - any deadline-tight lane -> <=2: the sweep acts at block
          boundaries, so a big block would overshoot the deadline;
        - a streaming consumer with NO queue pressure -> <=2: keep ITL
          smooth when latency is what the caller is buying;
        - otherwise (throughput pressure, batch-style ``.result()``
          consumers) the full ``decode_block`` ceiling;
        - never longer than the largest remaining step budget needs
          (covering it with one block instead of trailing short blocks).
        """
        kmax = self.decode_block
        if kmax <= 1:
            return 1
        now = _time.monotonic()
        want = kmax
        streaming = False
        max_rem = 1
        lag = lag or {}
        for lane, req in decode_lanes:
            sp = req.sampling
            if sp.temperature > 0.0 and not sp.device:
                return 1
            if (req.deadline is not None
                    and req.deadline - now < self._tight_slack_s()):
                want = min(want, 2)
            if req.on_token is not None and not req.batch:
                # batch lanes run throughput-optimized: their on_token
                # hook is a durable checkpoint sink, not an interactive
                # consumer — never let it drag the whole block to K<=2
                streaming = True
            max_rem = max(max_rem, req.steps - len(req.tokens_out)
                          - lag.get(lane, (0, 0))[1])
        if streaming and not self._queue:
            want = min(want, 2)
        cover = next((m for m in self.BLOCK_K_MENU if m >= max_rem),
                     self.BLOCK_K_MENU[-1])
        k = min(want, cover)
        return max(m for m in self.BLOCK_K_MENU if m <= k)

    def _reserve_block_pages(self, decode_lanes, k: int, lag=None):
        """Pre-allocate every page the next K appends will write, per lane.

        Decode step j writes K/V at position ``length + j`` — the device
        cannot allocate, so the block table must cover the whole block
        BEFORE dispatch.  Appends land at positions >= the prompt length,
        which always sit in the lane's private pages (the prefix cache
        only ever shares FULL prompt pages strictly below the write
        region), so pre-allocation can never hand the block a shared
        page to write.  Under pool pressure the block shrinks to what
        every participating lane can cover (snapped down onto
        BLOCK_K_MENU, surplus pages returned); a lane that cannot cover
        even one append skips this block entirely (same as the old
        per-tick starvation skip).  ``lag`` (lane -> (positions,
        tokens)) is what a dispatch still in flight gives a lane: its
        committed length and step budget lag the block being reserved by
        that much, so it writes from ``length + positions`` (a lane then
        holds pages for at most two blocks past its committed length).
        Returns ``(k_eff, [(lane, req, new_pages), ...])``.
        """
        ps = self.page_size
        parts = []
        cap = k
        lag = lag or {}
        for lane, req in decode_lanes:
            ahead, ahead_tok = lag.get(lane, (0, 0))
            base = req.length + ahead
            rem = req.steps - len(req.tokens_out) - ahead_tok
            # a lane stops where its EVA window ends (the device masks it
            # there): the block writes no row past the boundary
            appends_want = max(1, min(k, rem, self._boundary(req) - base))
            base = self._rows(req, base)
            need = (base + appends_want - 1) // ps + 1
            new: List[int] = []
            while len(req.pages) < need:
                page = self._alloc_page(req.pages[-1] if req.pages else 0)
                if page is None:
                    break
                req.pages.append(page)
                new.append(page)
            appends = min(appends_want, len(req.pages) * ps - base)
            if appends <= 0:
                for _ in new:  # starved: return the partial take
                    self.pool.release_pages([req.pages.pop()])
                continue
            if appends < appends_want:
                cap = min(cap, appends)
            if self.wpool is not None:
                # the window group moves with the lane: behind its
                # COMMITTED length (what is in flight holds its own table),
                # ahead to the last row this block may write
                self._window_pages(req, req.length, base + appends - 1)
            parts.append((lane, req, new))
        if not parts:
            return k, []
        k_eff = max(m for m in self.BLOCK_K_MENU if m <= max(1, cap))
        if k_eff < k:
            # shrunk block: give back pages past the new write horizon
            for lane, req, new in parts:
                ahead, ahead_tok = lag.get(lane, (0, 0))
                base = req.length + ahead
                rem = req.steps - len(req.tokens_out) - ahead_tok
                want = max(1, min(k_eff, rem, self._boundary(req) - base))
                need = (self._rows(req, base) + want - 1) // ps + 1
                while len(req.pages) > need and new:
                    self.pool.release_pages([req.pages.pop()])
                    new.pop()
        return k_eff, parts

    def _spec_eligible(self, req: _PagedRequest) -> bool:
        """May this lane ride a speculative dispatch?  Host-sampled
        (``top_k``/``top_p``/host-PRNG temperature) lanes never enter the
        speculative path — their picks need the logits row on host every
        token; degraded lanes (chaos verify trip, acceptance EWMA under
        the floor) stay plain for the rest of the request."""
        sp = req.sampling
        if sp.temperature > 0.0 and not sp.device:
            return False
        return req.spec_enabled

    def _degrade_spec(self, req: _PagedRequest,
                      probe: bool = False) -> None:
        """Drop the lane to plain decode blocks; its draft-table pages go
        straight back to the pool.  ``probe=True`` (the acceptance-EWMA
        path) schedules a periodic re-try: after ``SPEC_PROBE_INTERVAL``
        plain dispatches the lane runs ONE speculative probe block and
        recovers if acceptance came back — a transient degrade (an
        out-of-distribution stretch, a cold stretch after resume) stops
        being forever.  ``probe=False`` (chaos verify trips) stays plain
        for the rest of the request, as before."""
        if req.spec_enabled:
            req.spec_enabled = False
            self.spec_fallbacks += 1
        req.spec_probe_in = self.SPEC_PROBE_INTERVAL if probe else None
        req.spec_probing = False
        if req.draft_pages:
            self.pool.release_pages(req.draft_pages)
            req.draft_pages = []
        req.draft_len = 0

    def _probe_countdown_locked(self, req: _PagedRequest) -> None:
        """One plain dispatch elapsed for a transiently degraded lane.
        When the countdown hits zero the lane re-enters speculation as a
        PROBE: its EWMA is reset to the floor so the probe block's own
        acceptance decides — >= floor recovers the lane, < floor
        re-degrades and re-schedules the next probe."""
        if (self._spec is None or req.spec_enabled
                or req.spec_probe_in is None):
            return
        req.spec_probe_in -= 1
        if req.spec_probe_in > 0:
            return
        req.spec_probe_in = None
        req.spec_enabled = True
        req.spec_probing = True
        req.spec_ewma = self.spec_accept_floor
        self.spec_probes += 1

    def _reserve_spec_pages(self, decode_lanes, k: int):
        """Target + draft page reservation for one speculative block.

        A spec block writes ``k + 1`` positions (``lengths .. lengths+k``)
        on BOTH tables and emits up to ``k + 1`` accepted tokens.  Target
        pages are reserved FIRST (the plain fallback needs them
        regardless); under pool pressure the DRAFT table's shortfall
        shrinks the block k — it never steals or releases target pages.
        Pages past the (possibly shrunk) write horizon go straight back
        to the pool.  Returns ``(kd, parts)`` with ``parts`` entries
        ``(lane, req, new_target_pages, new_draft_pages)``; ``kd == 0``
        means the pool cannot support speculation this dispatch — the
        caller falls back to the plain path (surviving target
        reservations stay on the lanes for it, draft takes are
        returned)."""
        parts = []
        cap = k + 1                   # min covered appends across lanes
        for lane, req in decode_lanes:
            rem = req.steps - len(req.tokens_out)
            want = max(1, min(k + 1, rem))
            need = (req.length + want - 1) // self.page_size + 1
            new_t: List[int] = []
            while len(req.pages) < need:
                page = self._alloc_page(req.pages[-1] if req.pages else 0)
                if page is None:
                    break
                req.pages.append(page)
                new_t.append(page)
            cov_t = len(req.pages) * self.page_size - req.length
            if cov_t <= 0:
                for _ in new_t:   # starved: return the partial take
                    self.pool.release_pages([req.pages.pop()])
                continue
            new_d: List[int] = []
            while len(req.draft_pages) < need:
                page = self._alloc_page(
                    req.draft_pages[-1] if req.draft_pages else 0)
                if page is None:
                    break
                req.draft_pages.append(page)
                new_d.append(page)
            cov_d = len(req.draft_pages) * self.page_size - req.length
            # only a COVERAGE shortfall shrinks the block: a lane whose
            # step budget is smaller than the block is handled by the
            # device-side steps-remaining mask (writes past the budget
            # route to scratch), exactly like plain blocks
            if cov_t < want:
                cap = min(cap, cov_t)
            if cov_d < want:
                cap = min(cap, cov_d)
            parts.append((lane, req, new_t, new_d))
        if not parts or cap < 2:
            # cannot cover even one proposal + its verify write: hand the
            # draft takes back; target reservations stay for plain blocks
            for _lane, req, _new_t, new_d in parts:
                for _ in new_d:
                    self.pool.release_pages([req.draft_pages.pop()])
            return 0, []
        kd = max(m for m in self.BLOCK_K_MENU if m <= cap - 1)
        for _lane, req, new_t, new_d in parts:
            rem = req.steps - len(req.tokens_out)
            want = max(1, min(kd + 1, rem))
            need = (req.length + want - 1) // self.page_size + 1
            while len(req.pages) > need and new_t:
                self.pool.release_pages([req.pages.pop()])
                new_t.pop()
            while len(req.draft_pages) > need and new_d:
                self.pool.release_pages([req.draft_pages.pop()])
                new_d.pop()
        return kd, parts

    def _plan_decode(self, snapshot):
        """Pick this dispatch's lanes, mode (speculative vs plain), block
        size, and page reservations.  The dispatch is speculative iff a
        draft model is armed and EVERY participating lane is eligible
        (one fused program serves the whole batch); otherwise — or when
        pool pressure cannot cover the draft tables — it is a plain
        block, which is the adaptive fallback the menu pick feeds."""
        decode_lanes = [(lane, req) for lane, req in enumerate(snapshot)
                        if req is not None and not req.cancelled
                        and not req.pending_prompt and req.tokens_out]
        if not decode_lanes:
            return None
        k = self._pick_block_k(decode_lanes)
        if (self._spec is not None
                and all(self._spec_eligible(r) for _, r in decode_lanes)):
            kd, parts = self._reserve_spec_pages(decode_lanes, k)
            if kd >= 1 and parts:
                return {"k": kd, "parts": parts, "mode": "spec"}
        k, parts = self._reserve_block_pages(decode_lanes, k)
        if not parts and any(req.draft_pages for _, req in decode_lanes):
            # every lane page-starved while draft tables hoard pages: the
            # draft KV is always regenerable, so treat pool pressure as a
            # TRANSIENT degrade — release the draft tables (arming the
            # probe countdown) and retry plain; without this the pool can
            # deadlock with target+draft tables holding every page
            for _lane, req in decode_lanes:
                if req.draft_pages:
                    self._degrade_spec(req, probe=True)
            k, parts = self._reserve_block_pages(
                decode_lanes, self._pick_block_k(decode_lanes))
        if not parts:
            return None  # every lane page-starved: caller backs off
        return {"k": k, "parts": parts, "mode": "plain"}

    def _tick(self, snapshot, jnp) -> bool:
        """One scheduler decode pass: consume the dispatch in flight (a
        decode block or a mixed round, which enqueues its successor first)
        if there is one, else plan + dispatch + consume.  Returns True
        when any lane made progress, False when every decode lane is
        starved (pool pressure) or idle."""
        st = self._stages
        if self._pending_block is not None:
            stash, self._pending_block = self._pending_block, None
            if stash["kind"] == "round":
                return self._consume_round(stash, jnp)
            return self._consume_block(stash, jnp)
        with stage(st, "plan"):
            plan = self._plan_decode(snapshot)
        if plan is None:
            return False
        if plan["mode"] == "spec":
            with stage(st, "dispatch"):
                stash = self._dispatch_spec_block(plan["parts"], plan["k"],
                                                  jnp)
            if stash is not None:
                return self._consume_spec_block(stash, jnp)
            # verify trip (chaos) pre-dispatch: the lanes just degraded to
            # plain — re-plan this tick as a plain block (their target
            # reservations are already in place)
            with stage(st, "plan"):
                lanes = [(lane, req)
                         for lane, req, _nt, _nd in plan["parts"]]
                k, parts = self._reserve_block_pages(
                    lanes, self._pick_block_k(lanes))
            if not parts:
                return False
            plan = {"k": k, "parts": parts, "mode": "plain"}
        if plan["k"] == 1:
            return self._tick_single(plan["parts"], jnp)
        with stage(st, "dispatch"):
            stash = self._dispatch_block(plan["parts"], plan["k"], jnp)
        return self._consume_block(stash, jnp)

    def _dispatch_block(self, parts, k: int, jnp, carry=None,
                        host=None, ahead: int = 0):
        """Issue one K-step fused decode dispatch (async — no host sync),
        inside the caller's ``dispatch`` stage.

        ``carry`` chains the block behind a dispatch in flight (a block or
        a mixed round) from its device-resident final state
        (dispatch-ahead overlap): the block table is rebuilt host-side
        either way (new pages may have been reserved) and travels with the
        sampling and stop arrays (``host``: a predecessor block's, reused;
        None: built from the lanes) in the block's ONE buffer, whose
        ``fresh`` flags are off, so lengths/tokens/live/steps-remaining
        are the carry's and chaining costs no round trip.  A chain's first
        block sends all of it, every lane ``fresh``, beside a carry nobody
        reads: the same program.  ``ahead`` (the predecessor's steps where
        it is not fetched yet) counts the block as enqueued ahead.
        """
        clock = self._stages
        with part(clock, "dispatch.arrays"):
            b = self.lanes
            lane_reqs = {lane: req for lane, req, _new in parts}
            tables = self._lane_tables(lane_reqs.items())
            lengths = np.zeros((b,), np.int32)
            tokens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            rem = np.zeros((b,), np.int32)
            if carry is None:
                for lane, req, _new in parts:
                    lengths[lane] = req.length
                    tokens[lane] = req.tokens_out[-1]
                    active[lane] = True
                    rem[lane] = req.steps - len(req.tokens_out)
            if host is None:
                temps = np.zeros((b,), np.float32)
                seeds = np.zeros((b, 2), np.uint32)   # (lo, hi) words
                n_stop = max((len(r.stop_tokens) for _, r, _ in parts),
                             default=0)
                width = ((1 << (n_stop - 1).bit_length()) if n_stop > 1
                         else 1)
                stops = np.full((b, width), -1, np.int32)  # ids >= 0: pad safe
                for lane, req, _new in parts:
                    sp = req.sampling
                    if sp.device and sp.temperature > 0.0:
                        temps[lane] = sp.temperature
                        seeds[lane] = (sp.seed & 0xFFFFFFFF,
                                       (sp.seed >> 32) & 0xFFFFFFFF)
                    if req.stop_tokens:
                        st = sorted(req.stop_tokens)
                        stops[lane, :len(st)] = st
            else:
                temps, seeds, stops = host
            buf = pack_words(self.programs.fields["block"], dict(
                tables, lengths=lengths, tokens=tokens, active=active,
                temps=temps, seeds=seeds, rem=rem, stops=stops,
                fresh=np.full((b,), carry is None)))
        # chaos: decode fault site — tripped once per DECODE TICK (k times
        # per block), so a deterministic schedule written against
        # per-token serving (error@N, per-tick delays) keeps its meaning
        # under fused blocks; an error fails the in-flight requests and
        # resets the pool (the scheduler's recovery path)
        for _ in range(k):
            chaos.trip("engine.step")
        t0 = _time.perf_counter()
        with part(clock, "dispatch.put"):
            packed = self._put(buf)
        with part(clock, "dispatch.call"):
            (out, len_f, tok_f, live_f, rem_f,
             self._kv_state) = self.programs.block(k)(
                self.params, self._kv_state, packed,
                carry or self._no_carry)
            ticket = clock.launched()
            out.copy_to_host_async()
        clock.note(program=self.programs.block_names[k], k=k,
                   lanes=len(lane_reqs), rows=len(lane_reqs),
                   ahead=int(ahead > 0))
        self.decode_dispatches += 1
        self.decode_block_steps += k
        self.ahead_blocks += ahead > 0
        self._note_walk(lane_reqs.values(), k)
        self._note_dispatch("decode")
        return {"kind": "block", "k": k, "lane_reqs": lane_reqs, "out": out,
                "carry": (len_f, tok_f, live_f, rem_f),
                "host": (temps, seeds, stops), "next": lane_reqs,
                "ahead": dict.fromkeys(lane_reqs, (k, k)), "t0": t0,
                "ticket": ticket}

    def _chain_block(self, stash, jnp, ahead: int):
        """Enqueue the dispatch that follows ``stash`` (a decode block or
        a mixed round) from its device-resident carry: ``(dispatch,
        None)``, or ``(None, cause)`` where the chain must break, ``cause``
        one of ``BREAK_CAUSES``.

        **The rule for the successor**: while any admitted request has
        prompt tokens that the dispatches so far do not carry, it is a
        mixed round (:meth:`_dispatch_round`) with the budget's chunks,
        oldest admission first, and EVERY lane that decodes once ``stash``
        is done as a row: the lanes of a block, the decode rows of a round,
        and the lanes whose prompt ends in that round (their first token is
        still on the device).  Such a round IS the decoding lanes' step: no
        block is owed behind it.  Otherwise it is the K-block
        :meth:`_pick_block_k` picks.

        Called by :meth:`_consume_block` and :meth:`_consume_round` BEFORE
        the fetch (``ahead`` = the dispatch's steps, 1 for a round: its
        tokens are not committed, so the lanes' lengths and step budgets
        lag by ``stash["ahead"]``): the successor goes on the device's
        queue behind ``stash``, and the fetch, commit and emit of ``stash``
        overlap it.  Everything the decision needs the host has known
        since it enqueued ``stash``: the lane set is still its own (cancel,
        deadline sweep and preemption are host events; a queued request
        is admitted first, as the commit would), no decoding lane stands
        outside it, and no lane's step budget ends inside it — a
        completion the host can foresee breaks the chain, so the freed
        lane is re-admitted before the next dispatch, never one later.  A
        stop token it cannot foresee ends a lane with the successor
        already in flight: the carry's live mask is false for it there (a
        block's writes go to the scratch page, a round holds no row for
        it) and the consume discards a lane that is no longer the
        dispatch's.  The chain also breaks where a lane stands at its EVA
        window's end (the compaction goes where no writer is in flight),
        where a lane's pick is made on the host (``host``: its token is
        not in the carry; also a round that ends a prompt ``kv_publish``
        exports: the snapshot is taken before the next write), where a
        round's pick was a resumed request's
        (``resumed``: discarded, the carry's token is not the lane's) and
        where a lane has more stop ids than a round's buffer holds
        (``stops``).  By :meth:`_consume_block` once more AFTER the commit
        (``ahead`` = 0): the same rule gives the old order, for a block
        the first call held back and the commit cleared (a deadline or a
        queue the K policy saw, pages that came home) while no prompt
        waits.  Never shrinks K: pages reserved for a refused block stay
        on the lanes for the next regular plan (bounded hoard: two blocks
        a lane)."""
        st = self._stages
        k, after_round = stash["k"], stash["kind"] == "round"
        if k <= 1 and not after_round:
            return None, "k1"
        lag = stash["ahead"] if ahead else {}
        lanes_now = list(stash["next"].items())
        with stage(st, "plan"):
            with self._cv:
                if self._shutdown or self._hbm_reclaim_bytes:
                    return None, "shutdown_or_reclaim"
                if ahead and self._queue:
                    # a request that arrived since the top of _run takes
                    # its lane now, not at the commit after the fetch:
                    # left queued it reads as queue pressure to
                    # _pick_block_k and holds this block back for a fetch
                    with stage(st, "admit"):
                        self._admit_locked()
                for lane, req in lanes_now:
                    # released (cancel/deadline sweep, a stop token) or
                    # preempted since dispatch
                    if self._active[lane] is not req or req.cancelled:
                        return None, "released"
                for lane, req in lanes_now:    # about to complete
                    if (req.steps - len(req.tokens_out)
                            <= lag.get(lane, (0, 0))[1]):
                        return None, "completion"
                for lane, req in lanes_now:
                    # may have reached the end of its EVA window in the
                    # dispatch in flight: its rows are compacted before
                    # another is written
                    if (req.length + lag.get(lane, (0, 0))[0]
                            >= self._boundary(req)):
                        return None, "compact"
                waiting = [r for r in self._active
                           if r is not None and r.pending_prompt
                           and not r.cancelled]
                # a decoding lane outside the chain (swapped back in, or
                # left out of a round for want of a page; soon: a snapshot
                # about to be swapped in): chaining on would leave it
                # without a step until a lane of the chain completes
                if any(r is not None and lane not in stash["next"]
                       and not r.pending_prompt and r.tokens_out
                       and not r.cancelled
                       for lane, r in enumerate(self._active)) or any(
                           r.kv_handle is not None for r in waiting):
                    return None, "joiner"
                # a prompt that stands at its window's end waits for its
                # compaction, which goes where no writer is in flight
                if any(r.pf_started and r.length >= self._boundary(r)
                       for r in waiting):
                    return None, "compact"
                snapshot = list(self._active)
            if after_round and ahead:
                # what a round's carry cannot hold of a lane
                if (any(self._host_sampled(r) for _, r in lanes_now)
                        or any(self._publishes(r, resumed)
                               for _l, r, resumed in stash["firsts"])):
                    return None, "host"
                if any(resumed for _l, _r, resumed in stash["firsts"]):
                    return None, "resumed"
                if any(len(r.stop_tokens) > ROUND_STOPS
                       for _, r in lanes_now):
                    return None, "stops"
            # a lane that re-armed speculation (a probe countdown expired)
            # must flow back through _plan_decode — a plain chain here
            # would starve the probe forever
            if (self._spec is not None
                    and all(self._spec_eligible(r) for _, r in lanes_now)):
                return None, "spec"
            if waiting:
                if not ahead:
                    # a link after the commit is a block's: the next pass
                    # heads a chain with the round
                    return None, "joiner"
                segs, _ = self._round_segments(snapshot)
                if segs:
                    # a round behind an un-fetched predecessor carries
                    # every decoding lane or none
                    rows = self._round_rows(lanes_now, lag)
                    if len(rows) != len(lanes_now):
                        return None, "pages"
                    with stage(st, "dispatch"):
                        return self._dispatch_round(segs, rows,
                                                    chain=stash), None
            k2 = self._pick_block_k(lanes_now, lag)
            if after_round:
                if k2 <= 1:
                    return None, "k1"
            elif k2 != k:
                return None, "k"
            k3, parts = self._reserve_block_pages(lanes_now, k2, lag)
            if k3 != k2 or len(parts) != len(lanes_now):
                return None, "pages"
        with stage(st, "dispatch"):
            return self._dispatch_block(
                parts, k2, jnp, carry=stash["carry"], host=stash["host"],
                ahead=ahead), None

    def _consume_block(self, stash, jnp) -> bool:
        """Fetch a dispatched block (ONE host sync for up to K tokens per
        lane) and unpack it through the per-token emit/trace/metrics
        path.  The dispatch behind it (block N+1, or a mixed round where a
        prompt waits) is enqueued first where :meth:`_chain_block` allows,
        so the device computes it while the host fetches, commits and
        emits block N; two dispatches are un-fetched only inside this
        method and :meth:`_consume_round` (one at the top of ``_run``'s
        loop, as ever).  Correctness never depends on the chain: a request
        released between dispatch and consume has its block discarded
        below, and its stale device writes only touch positions a new page
        owner rewrites before reading."""
        st = self._stages
        k = stash["k"]
        self._pending_block, why = self._chain_block(stash, jnp, ahead=k)
        if why is not None:
            self.chain_breaks[why] += 1
        with stage(st, "fetch"):
            res = self._results(stash["out"], k)
            toks, lps, ems = res["tokens"], res["logprobs"], res["emitted"]
            self._note_moe(res.get("moe"), decode=True)
            st.landed(stash["ticket"], why, lanes=len(stash["lane_reqs"]))
        self.decode_host_syncs += 1
        now = _time.perf_counter()  # post-fetch: device work is done
        # a block enqueued ahead started when its predecessor ended, which
        # the host saw as the previous fetch's return
        step_s = (now - max(stash["t0"], self._block_fetched_t)) / k
        self._block_fetched_t = now
        self._step_ewma_s = (0.8 * self._step_ewma_s + 0.2 * step_s
                             if self._step_ewma_s else step_s)
        emits: List = []
        completed: List = []
        with stage(st, "commit"), self._cv:
            for lane, req in stash["lane_reqs"].items():
                if (self._active[lane] is not req or req.cancelled
                        or req.pending_prompt):
                    # released (cancel/deadline sweep) or preempted since
                    # dispatch (and maybe on a lane again, its prompt to
                    # fill): its block tokens are DISCARDED — a resume
                    # regenerates them exactly, a cancel never emits them
                    continue
                self._probe_countdown_locked(req)
                n = int(ems[lane].sum())   # prefix mask: first n are valid
                if n == 0:
                    continue
                self._note_rows("decode", req.length, n)
                # the block is one device round trip: spread its wall time
                # evenly over the lane's tokens so ITL keeps a true mean
                # (the burst shape is documented in docs/PERFORMANCE.md)
                dt = (now - req.t_last) / n if req.t_last is not None \
                    else None
                self._note_second_token(req, now)
                for j in range(n):
                    tok = int(toks[lane, j])
                    req.length += 1
                    req.tokens_out.append(tok)
                    self.tokens_generated += 1
                    if self.metrics is not None and dt is not None:
                        self.metrics.observe_itl(dt)
                    lp = float(lps[lane, j]) if req.want_logprobs else None
                    if req.want_logprobs:
                        req.logprobs_out.append(lp)
                    emits.append((req, tok, len(req.tokens_out) - 1, lp))
                req.t_last = now
                self._fl_block(req, k, n, dt)
                self._flush_decode_chunk(req, lane, now, block=k)
                if req.finished():
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        if self._pending_block is None:
            self._pending_block, why = self._chain_block(stash, jnp, ahead=0)
            if why is None:
                self.late_links += 1
        self._deliver(emits, completed)
        return True

    # -- speculative decode dispatch -----------------------------------------
    SPEC_EWMA_DECAY = 0.5   # per-dispatch acceptance EWMA smoothing

    #: plain dispatches a transiently degraded lane (acceptance EWMA under
    #: the floor) waits before one speculative PROBE block re-tries it;
    #: chaos-verify degrades never probe (plain for the rest of the request)
    SPEC_PROBE_INTERVAL = 4

    def _warm_draft(self, req: _PagedRequest, jnp) -> None:
        """Bring the lane's draft KV up to the target context (positions
        ``[draft_len, length)``): one fused draft forward over the
        missing tail, scattered through the SECOND page table.  Costs a
        dispatch but never a host sync (the logits are not fetched).
        Runs at first speculative entry, after a preemption resume (the
        draft table is released at preemption and regenerated exactly
        here), and after plain-block interludes."""
        t = req.length
        if req.draft_len >= t:
            return
        ctx = np.concatenate([req.prompt,
                              np.asarray(req.tokens_out[:-1], np.int32)])
        start = req.draft_len
        m = t - start
        m_pad = 1 << (m - 1).bit_length()
        tokens = np.zeros((1, m_pad), np.int32)
        tokens[0, :m] = ctx[start:t]
        tables = np.zeros((self.max_pages,), np.int32)
        tables[:len(req.draft_pages)] = req.draft_pages
        _last, self.pool.kv = self.programs.draft_extend(
            self._spec["params"], self.pool.kv, self._put(tables),
            self._put(tokens), self._put(np.int32(start)),
            self._put(np.int32(t)))
        req.draft_len = t
        self.spec_draft_prefills += 1

    def _dispatch_spec_block(self, parts, k: int, jnp):
        """Issue one fused speculative dispatch (draft-propose + verify +
        on-device accept).  Returns None when the verify trip point
        fires (chaos): the participating lanes degrade to plain blocks
        for the rest of their requests and NOTHING was dispatched — no
        token is ever emitted twice, corrupted, or lost."""
        # chaos: the speculative verify fault site — tripped once per
        # speculative dispatch, BEFORE anything is issued, so error/drop
        # degrade cleanly (the lanes' plain fallback re-decodes the very
        # same positions).  Exercised like kvcache.swap: degradation, not
        # request failure.
        try:
            tripped = chaos.trip("engine.verify")
        except chaos.ChaosError:
            tripped = "error"
        if tripped is not None:
            for _lane, req, _nt, _nd in parts:
                self._degrade_spec(req)
            return None
        for _lane, req, _nt, _nd in parts:
            self._warm_draft(req, jnp)
        b = self.lanes
        tables = np.zeros((b, self.max_pages), np.int32)
        dtables = np.zeros((b, self.max_pages), np.int32)
        lengths = np.zeros((b,), np.int32)
        tokens = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        temps = np.zeros((b,), np.float32)
        seeds = np.zeros((b, 2), np.uint32)   # (lo, hi) words
        rem = np.zeros((b,), np.int32)
        n_stop = max((len(r.stop_tokens) for _, r, _nt, _nd in parts),
                     default=0)
        width = (1 << (n_stop - 1).bit_length()) if n_stop > 1 else 1
        stops = np.full((b, width), -1, np.int32)  # ids >= 0: pad safe
        lane_reqs = {}
        for lane, req, _nt, _nd in parts:
            lane_reqs[lane] = req
            tables[lane, :len(req.pages)] = req.pages
            dtables[lane, :len(req.draft_pages)] = req.draft_pages
            lengths[lane] = req.length
            tokens[lane] = req.tokens_out[-1]
            active[lane] = True
            rem[lane] = req.steps - len(req.tokens_out)
            sp = req.sampling
            if sp.device and sp.temperature > 0.0:
                temps[lane] = sp.temperature
                seeds[lane] = (sp.seed & 0xFFFFFFFF,
                               (sp.seed >> 32) & 0xFFFFFFFF)
            if req.stop_tokens:
                st = sorted(req.stop_tokens)
                stops[lane, :len(st)] = st
        t0 = _time.perf_counter()
        (out, _len_f, _tok_f, _live_f, _rem_f,
         self.pool.kv) = self.programs.spec_block(k)(
            self.params, self._spec["params"], self.pool.kv,
            self._put(pack_words(self.programs.fields["spec"], dict(
                tables=tables, draft_tables=dtables, lengths=lengths,
                tokens=tokens, active=active, temps=temps, seeds=seeds,
                rem=rem, stops=stops))))
        ticket = self._stages.launched()
        out.copy_to_host_async()
        self.decode_dispatches += 1
        self.spec_dispatches += 1
        self._note_dispatch("verify")
        return {"k": k, "lane_reqs": lane_reqs, "out": out, "t0": t0,
                "ticket": ticket}

    def _consume_spec_block(self, stash, jnp) -> bool:
        """Fetch a speculative dispatch (ONE host sync for up to K+1
        accepted tokens per lane), update each lane's acceptance EWMA,
        and unpack through the per-token emit/trace/metrics path.
        Drafted-but-rejected proposals are counted (``spec_tokens_*``)
        but never emitted and never enter ``tokens_generated`` — so
        tokens-per-dispatch telemetry reflects accepted tokens only."""
        st = self._stages
        k = stash["k"]
        with stage(st, "fetch"):
            res = self._results(stash["out"], k + 1, spec=True)
            toks, lps, ems = res["tokens"], res["logprobs"], res["emitted"]
            drafted, accepted = res["drafted"], res["accepted"]
            st.landed(stash["ticket"], lanes=len(stash["lane_reqs"]))
        self.decode_host_syncs += 1
        now = _time.perf_counter()
        self._step_ewma_s = (
            0.8 * self._step_ewma_s + 0.2 * ((now - stash["t0"]) / (k + 1))
            if self._step_ewma_s else (now - stash["t0"]) / (k + 1))
        emits: List = []
        completed: List = []
        with stage(st, "commit"), self._cv:
            for lane, req in stash["lane_reqs"].items():
                if self._active[lane] is not req or req.cancelled:
                    continue  # released since dispatch: block discarded
                d, a = int(drafted[lane]), int(accepted[lane])
                self.spec_tokens_drafted += d
                self.spec_tokens_accepted += a
                req.spec_drafted += d
                req.spec_accepted += a
                rate = a / d if d else 0.0
                req.spec_ewma = (self.SPEC_EWMA_DECAY * req.spec_ewma
                                 + (1.0 - self.SPEC_EWMA_DECAY) * rate)
                if req.spec_probing:
                    # this dispatch WAS the probe: its acceptance decides
                    req.spec_probing = False
                    if req.spec_ewma >= self.spec_accept_floor:
                        self.spec_probe_recoveries += 1
                if req.spec_ewma < self.spec_accept_floor:
                    self._degrade_spec(req, probe=True)
                n = int(ems[lane].sum())   # prefix mask: first n are valid
                if n == 0:
                    continue
                dt = (now - req.t_last) / n if req.t_last is not None \
                    else None
                self._note_second_token(req, now)
                for j in range(n):
                    tok = int(toks[lane, j])
                    req.length += 1
                    req.tokens_out.append(tok)
                    self.tokens_generated += 1
                    if self.metrics is not None and dt is not None:
                        self.metrics.observe_itl(dt)
                    lp = float(lps[lane, j]) if req.want_logprobs else None
                    if req.want_logprobs:
                        req.logprobs_out.append(lp)
                    emits.append((req, tok, len(req.tokens_out) - 1, lp))
                req.t_last = now
                if req.draft_pages:
                    # the block's own draft writes cover every accepted
                    # position (k+1 scan iterations: no holes)
                    req.draft_len = req.length
                self._fl_block(req, k, n, dt)
                self._flush_decode_chunk(req, lane, now, block=k,
                                         accepted=a)
                if req.finished():
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        self._deliver(emits, completed)
        return True

    def _tick_single(self, parts, jnp) -> bool:
        """K=1 decode tick (host-sampled lanes present, or decode_block=1):
        one dispatch + one fetch per token, the pre-block behavior."""
        st = self._stages
        with stage(st, "dispatch"):
            b = self.lanes
            tables = self._lane_tables((lane, req) for lane, req, _ in parts)
            lengths = np.zeros((b,), np.int32)
            tokens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            # device-sampled lanes carry their temperature into the step (the
            # tick then fetches only (B,)-sized arrays for them); host-sampled
            # (top_k/top_p) lanes keep temp 0 on device and pick from fetched
            # logits rows
            temps = np.zeros((b,), np.float32)
            seeds = np.zeros((b, 2), np.uint32)   # (lo, hi) words
            host_lanes = []
            lane_reqs = {}
            for lane, req, _new in parts:
                lane_reqs[lane] = req
                tokens[lane] = req.tokens_out[-1]
                lengths[lane] = req.length
                active[lane] = True
                sp = req.sampling
                if sp.temperature > 0.0:
                    if sp.device:
                        temps[lane] = sp.temperature
                        seeds[lane] = (sp.seed & 0xFFFFFFFF,
                                       (sp.seed >> 32) & 0xFFFFFFFF)
                    else:
                        host_lanes.append(lane)
            # chaos: decode-tick fault site — an error fails the in-flight
            # requests and resets the pool (the scheduler's recovery path); a
            # delay makes every lane's step slow (deadline-storm scenarios)
            chaos.trip("engine.step")
            t0 = _time.perf_counter()
            out, logits, self._kv_state = self.programs.tick(
                self.params, self._kv_state,
                self._put(pack_words(self.programs.fields["tick"], dict(
                    tables, lengths=lengths, tokens=tokens,
                    active=active, temps=temps, seeds=seeds))))
            ticket = st.launched()
            out.copy_to_host_async()
            self.decode_dispatches += 1
            self.decode_block_steps += 1
            self._note_dispatch("decode")
        with stage(st, "fetch"):
            # greedy + device-sampled lanes: ONLY (B,)-sized arrays cross the
            # link (token ids + chosen-token logprobs), as one
            res = self._results(out)
            next_tokens = res["tokens"].copy()
            logprobs_arr = res["logprobs"].copy()
            self.decode_host_syncs += 1
            self._note_moe(res.get("moe"), decode=True)
            if host_lanes:
                # fetch ONLY the host-sampled rows: gather them device-side,
                # then one (n_host, vocab) transfer — not the full
                # (lanes, vocab) matrix when a single lane host-samples.
                # Only active host-sampled lanes consume PRNG state: a
                # page-starved or pending-prefill lane must not perturb a
                # seeded request's token sequence (per-request reproducibility)
                rows = self._fetch(
                    logits[self._put(np.asarray(host_lanes, np.int32))])
                self.decode_host_syncs += 1
                for i, lane in enumerate(host_lanes):
                    req = lane_reqs[lane]
                    next_tokens[lane] = req.sampling.pick(rows[i])
                    if req.want_logprobs:
                        # f32 log-sum-exp: the same precision class as the
                        # device log_softmax used for prefill and for
                        # device-sampled lanes — one request, one precision
                        row = rows[i].astype(np.float32)
                        row = row - row.max()
                        logprobs_arr[lane] = float(
                            row[next_tokens[lane]]
                            - np.log(np.exp(row).sum()))
            st.landed(ticket, "single", lanes=len(lane_reqs))

        emits: List = []
        completed: List = []
        now = _time.perf_counter()  # post-fetch: the tick's device work is
        #                             done, so per-lane deltas are real
        self._step_ewma_s = (0.8 * self._step_ewma_s + 0.2 * (now - t0)
                             if self._step_ewma_s else now - t0)
        with stage(st, "commit"), self._cv:
            for lane, req in lane_reqs.items():
                if req.cancelled:
                    continue  # the _run sweep releases it next round
                self._probe_countdown_locked(req)
                self._note_second_token(req, now)
                self._note_rows("decode", req.length, 1)
                req.length += 1
                req.tokens_out.append(int(next_tokens[lane]))
                self.tokens_generated += 1
                if self.metrics is not None and req.t_last is not None:
                    self.metrics.observe_itl(now - req.t_last)
                self._fl_block(req, 1, 1,
                               (now - req.t_last)
                               if req.t_last is not None else None)
                req.t_last = now
                lp = float(logprobs_arr[lane]) if req.want_logprobs else None
                if req.want_logprobs:
                    req.logprobs_out.append(lp)
                emits.append((req, req.tokens_out[-1],
                              len(req.tokens_out) - 1, lp))
                done = req.finished()
                if (done or len(req.tokens_out) - req.chunk_start
                        >= self.TRACE_DECODE_CHUNK):
                    self._flush_decode_chunk(req, lane, now)
                if done:
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        self._deliver(emits, completed)
        return True

    @staticmethod
    def _result_of(req: _PagedRequest):
        toks = list(req.tokens_out[:req.steps])
        if req.want_logprobs:
            return toks, list(req.logprobs_out[:len(toks)])
        return toks

    def _release_lane_locked(self, lane: int, req: _PagedRequest) -> None:
        if (req.export_digest is not None and self.kv_offload is not None
                and not req.cancelled and req.length > 0
                and req.finished()):
            # disagg export: demote the finished KV to the host tier
            # BEFORE the pages are released (dispatch order makes the
            # gather safe — same window as preemption swap-out).  The
            # handle rides the future; the shipper's export wait is the
            # write-behind fence.
            needed = (req.length + self.page_size - 1) // self.page_size
            req.future._tpulab_kv_export = self.kv_offload.swap_out(
                req.pages[:needed], req.length, self.pool.kv,
                key=("ship", req.export_digest))
        self.last_release = {"lane": lane, "pages": list(req.pages),
                             "length": req.length}
        if self.wpool is not None:
            # what the window group still held of it: the pages of table
            # entries ``[window_first, ...)``, position ``window_first *
            # page_size`` on
            self.last_release.update(window_pages=list(req.wpages),
                                     window_first=req.wfirst)
            self.wpool.release_pages(req.wpages)
            req.wpages = []
        self.pool.release_pages(req.pages)
        if req.draft_pages:
            self.pool.release_pages(req.draft_pages)
            req.draft_pages = []
        self._discard_handle(req)  # a cancelled resume never restores
        self._active[lane] = None
        self._requests.pop(req.future, None)
