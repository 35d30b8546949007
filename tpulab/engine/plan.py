"""The engine plan: what a :class:`~tpulab.engine.paged.ContinuousBatcher`
will be, decided from its arguments before anything is allocated.

:func:`plan_engine` returns an :class:`EnginePlan` (the model's kinds, the
page store's geometry, ``use_kernel``, the round's token budget, the
keywords the step programs are keyed by) and raises, by name, every option
a kind of model refuses and every geometry the kernels' rules refuse.  It
builds no pool and no lane-state store, puts nothing on a device and starts
no thread, so a refusal has nothing to clean up and a decision is tested
without an engine (``tests/test_engine_plan.py``).
:func:`kernel_error` is the one place that knows which geometry rule of
:mod:`tpulab.ops` goes with which kind of model.  Nothing here imports the
scheduler.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from tpulab.engine.kv_pool import latent_page_shape
from tpulab.engine.paged_steps import round_width
from tpulab.tpu import platform


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """What :func:`plan_engine` decided.  ``use_kernel`` is the
    constructor's argument (None: not chosen yet) while the budget is
    searched, a boolean in the plan it returns."""

    #: tpulab.models.spec.ModelSpec: None serves the dense decoder of
    #: ``n_heads``/``n_kv``/``rope_theta`` with today's constants
    spec: Optional[Any]
    lanes: int
    max_len: int
    page_size: int
    n_heads: int
    n_layers: int
    rope_theta: Optional[float]
    vocab: int
    compute_dtype: Any
    #: what a page stores: may be NARROWER than the compute path (e.g.
    #: float8_e4m3fn under bf16 compute halves KV HBM *and* decode bandwidth:
    #: the decode tick is KV-bandwidth-bound).  Writes round on scatter,
    #: reads upcast in the gather/kernel; attention math stays in f32
    kv_dtype: Any
    mesh: Optional[Any]
    #: model-axis shards of the page payloads (1 on one device)
    n_shards: int
    #: the kind of per-lane state ("mamba", "gdn", "cca", "mamba2"; None without
    #: layers that keep one); the page store then holds the attention layers
    #: (a CCA layer owns a layer of both)
    state_kind: Optional[str]
    #: a learned indexer: index rows beside the K/V pages, the pair rotated
    #: through every dispatch as a hybrid's (pages, state) is
    sparse: bool
    #: EVA: positions a window, and the rows a compaction takes off a
    #: lane's table (both 0 without: a row is a position).  With windows a
    #: lane's rows are not its positions, so nothing that takes a request's
    #: pages for its positions is carried
    eva_window: int
    eva_saved: int
    #: the latent cache entry's width (0: K and V rows)
    latent: int
    #: KV heads and head width of the pages (a hybrid's attention layers are
    #: the spec's: its KV heads size the pages)
    n_kv: int
    head_dim: int
    #: layers of the page store: only attention layers own one (with
    #: window layers: of its FIRST group, the full layers')
    pool_layers: int
    #: window layers beside full ones: the keys a window layer's row
    #: attends, and the layers of the page store's second group, theirs (0
    #: and 0 without: one group).  A lane's pages in that group are not its
    #: positions' (the pages behind the window go back while the request
    #: lives), so nothing that takes a request's pages for its positions is
    #: carried, as with EVA windows
    window: int
    window_layers: int
    #: an index key's width (0 without an indexer)
    index_dim: int
    #: a page table's width: it covers the most ROWS a lane of ``max_len``
    #: positions holds
    max_pages: int
    #: page-aligned (a chunk's successor writes from a page boundary)
    prefill_chunk: Optional[int]
    #: attention through the pallas ragged kernel family (the XLA gather
    #: otherwise); the dispatch plan is the same either way: prompts ride
    #: mixed rounds (docs/PERFORMANCE.md "Ragged paged attention")
    use_kernel: Optional[bool]
    #: the widest token budget of a mixed round THIS engine runs (a power
    #: of two), and what refused the next wider one (None at the ceiling)
    round_cap: int
    round_budget_why: Optional[str]
    #: the speculative draft's ``n_heads``, ``n_layers``, ``n_kv_heads``
    draft: Optional[Dict[str, int]] = None
    #: with the kernels on a latent store, the tile their plan gives a lane
    #: of the round's widest chunk (``"round"``) and of one row
    #: (``"one_row"``): ``heads`` a tile, ``walks`` of the lane's pages (its
    #: tiles), ``vmem_bytes`` a grid step
    latent_tile: Optional[Dict[str, Dict[str, int]]] = None

    @property
    def state_rule(self) -> Optional[Dict[str, str]]:
        """What runs a lane state's recurrence in the two programs that
        touch it, ``{"decode": ..., "round": ...}`` each ``"kernel"`` or
        ``"xla"`` (None without a lane state): with the kernels, a round
        scans its segments in one and a Gated DeltaNet layer's one-token
        rule is one too (a decode step, a round's decode rows); a Mamba
        layer's decode step is XLA in either plan, and a CCA layer's tails
        are XLA in both programs (its K/V walk is the page store's).  A
        Mamba-2 layer's one-token rule is a kernel with the kernels (a
        decode step, a round's decode rows) and its chunked form XLA in
        either plan (:mod:`tpulab.ops.ssd`)."""
        if not self.state_kind:
            return None
        if self.state_kind == "cca":
            return {"decode": "xla", "round": "xla"}
        form = "kernel" if self.use_kernel else "xla"
        if self.state_kind == "mamba2":
            return {"decode": form, "round": "xla"}
        return {"decode": form if self.state_kind == "gdn" else "xla",
                "round": form}

    @property
    def walk_block_pages(self) -> int:
        """Pages a key block of the attention kernels' page walk holds at
        this engine's shapes, by the kernels' own geometry: a block whose
        table entries are one ascending run of ids is one DMA."""
        from tpulab.engine.kv_pool import latent_page_shape
        from tpulab.ops.ragged_attention import walk_block_pages
        row = (latent_page_shape(self.page_size, self.latent)[-1]
               if self.latent
               else self.n_kv * self.head_dim // max(1, self.n_shards))
        return walk_block_pages(self.page_size, self.max_pages, row,
                                self.kv_dtype)

    def window_lane_pages(self, ahead: int) -> int:
        """The most pages of the window group ONE lane holds, in whole key
        blocks of the kernels' walk (the unit the scheduler takes and
        returns them in, so that a block it walks is one run of ids): the
        blocks that overlap ``(length - window, length + ahead]`` at their
        widest, ``ahead`` the rows a dispatch may write past the lane's
        committed length (a round's budget; two decode blocks in flight)."""
        g = self.walk_block_pages * self.page_size
        return (-(-(self.window + ahead) // g) + 1) * self.walk_block_pages

    @property
    def step_kw(self) -> Dict[str, Any]:
        """The keywords the step programs bind, and are keyed by in the
        process's program memo."""
        kw = dict(lanes=self.lanes, max_pages=self.max_pages,
                  n_heads=self.n_heads, n_layers=self.n_layers,
                  compute_dtype=self.compute_dtype,
                  use_kernel=self.use_kernel, n_kv_heads=self.n_kv,
                  rope_theta=self.rope_theta, mesh=self.mesh)
        if self.spec is not None:
            # only where a spec was given: a dense engine's programs keep
            # the key they always had in the memo
            kw["spec"] = self.spec
        return kw


def kernel_error(plan: EnginePlan, cap: int):
    """What the kernels' geometry rules say of ``plan`` (None: admitted):
    Mosaic's shape rule at the PER-SHARD geometry (one shard's program is
    the one that must build) and the widest segment a dispatch can carry: a
    mixed round that spends a token budget of ``cap`` (``prefill_chunk``
    lowers it)."""
    from tpulab.ops.ragged_attention import (kernel_geometry_error,
                                             latent_geometry_error)
    spec = plan.spec
    if plan.state_kind in ("mamba", "gdn"):
        from tpulab.ops.gated_delta_rule import rule_geometry_error
        from tpulab.ops.selective_scan import scan_geometry_error
        err = (scan_geometry_error(spec.d_inner, spec.d_state)
               if plan.state_kind == "mamba" else
               rule_geometry_error(spec.gdn_k_dim, spec.gdn_v_dim))
        if err:
            return err
    if plan.state_kind == "mamba2":
        from tpulab.ops.ssd import step_geometry_error
        err = step_geometry_error(spec.m2_head_dim, spec.m2_state)
        if err:
            return err
    if plan.eva_window:
        from tpulab.ops.eva_summary import summary_geometry_error
        err = summary_geometry_error(plan.head_dim, spec.eva_chunk,
                                     plan.page_size)
        if err:
            return err
    widest = round_width(min(plan.prefill_chunk or cap, cap))
    if plan.latent:
        return latent_geometry_error(*_latent_call(plan, widest))
    return kernel_geometry_error(
        widest, plan.n_heads // plan.n_shards, plan.n_kv // plan.n_shards,
        plan.head_dim, plan.page_size, plan.max_pages,
        plan.compute_dtype, plan.kv_dtype)


def _latent_call(plan: EnginePlan, q_len: int) -> tuple:
    """The shapes of ``plan``'s latent kernel call at ``q_len`` rows a lane,
    as ``latent_geometry_error`` and ``latent_tile`` take them."""
    return (q_len, plan.n_heads,
            latent_page_shape(plan.page_size, plan.latent)[2],
            plan.spec.kv_lora_rank, plan.page_size, plan.max_pages,
            plan.compute_dtype, plan.kv_dtype)


def plan_engine(*, spec, n_heads: int, n_layers: int,
                n_kv_heads: Optional[int], rope_theta: Optional[float],
                d_model: int, vocab: int, lanes: int, max_len: int,
                page_size: int, prefill_chunk: Optional[int],
                use_kernel: Optional[bool], compute_dtype, kv_dtype,
                round_ceiling: int, kernel_auto_min_ctx: int,
                pool=None, mesh=None, hbm=None, draft_params=None,
                draft_n_layers: Optional[int] = None,
                draft_n_heads: Optional[int] = None,
                draft_n_kv_heads: Optional[int] = None, kv_offload=None,
                kv_publish: bool = False,
                prefix_cache: bool = False) -> EnginePlan:
    """The :class:`EnginePlan` of a scheduler built with these arguments
    (``ContinuousBatcher``'s own, under their names).  ``d_model`` and
    ``vocab`` are the embedding's shape; ``round_ceiling`` and
    ``kernel_auto_min_ctx`` the scheduler class's ``RAGGED_CHUNK_CAP`` and
    ``KERNEL_AUTO_MIN_CTX``.  Of ``hbm`` and ``kv_offload`` it reads
    whether they are there, of
    ``draft_params`` the draft's width, of a provided ``pool`` its
    ``dtype``, ``entry_kind``, ``n_layers``, ``mesh`` and whether it has
    ``index`` rows."""
    import jax.numpy as jnp

    hybrid = spec is not None and bool(spec.state_layers)
    sparse = spec is not None and bool(spec.index_topk)
    eva = spec is not None and bool(spec.eva_window)
    windowed = spec is not None and bool(spec.window)
    special = spec is not None and (spec.cache_entry != "kv"
                                    or spec.moe_layers or hybrid or eva
                                    or windowed)
    if special:
        # the options that such a model's cache-entry kind or per-lane
        # state (nothing snapshots, shares or ships it yet) does not carry
        # are refused here
        refused = {
            "draft_params (speculative blocks)": draft_params is not None,
            "mesh": mesh is not None
            or getattr(pool, "mesh", None) is not None,
            "kv_offload": kv_offload not in (None, False),
            "kv_publish": bool(kv_publish),
            "prefix_cache": bool(prefix_cache),
            "kv_dtype other than the compute dtype":
                kv_dtype is not None
                and jnp.dtype(kv_dtype) != jnp.dtype(compute_dtype),
            "hbm (the elastic page store)": hbm is not None,
        }
        bad = [name for name, on in refused.items() if on]
        if bad:
            kinds = sorted({f"{spec.cache_entry} pages"}
                           | {f"{k} layers" for k in spec.mixers
                              if k not in ("attention", "none")}
                           | {f"{k} FFNs" for k in spec.layer_kinds
                              if k not in ("dense", "none")}
                           | ({"EVA windows (compacted pages)"} if eva
                              else set())
                           | ({"window layers (a page table a layer kind: "
                               "the pages behind the window go back while "
                               "the request lives)"} if windowed else set())
                           | ({"hyper-connected residual streams"}
                              if spec.hc_mult else set()))
            raise NotImplementedError(
                f"a model with {', '.join(kinds)} is not supported with: "
                + ", ".join(bad))
        if (spec.n_heads, spec.n_layers) != (n_heads, n_layers):
            raise ValueError(
                f"spec (n_heads {spec.n_heads}, n_layers {spec.n_layers})"
                f" disagrees with n_heads={n_heads}, n_layers={n_layers}")
        if eva and page_size != spec.eva_chunk:
            raise ValueError(
                f"page_size {page_size} is not the spec's eva_chunk "
                f"{spec.eva_chunk}: a chunk's summary is taken from one "
                "page and written as one row")
    kv_dtype = kv_dtype or compute_dtype
    n_kv = (spec.n_kv_heads if hybrid or sparse or eva or windowed
            else n_kv_heads or n_heads)
    eva_window = spec.eva_window if eva else 0
    max_pages = ((spec.cache_rows_peak(max_len) if eva else max_len)
                 + page_size - 1) // page_size
    if prefill_chunk is not None:
        if prefill_chunk < page_size:
            raise ValueError("prefill_chunk must be >= page_size")
        prefill_chunk -= prefill_chunk % page_size
    latent = (spec.latent_width
              if spec is not None and spec.cache_entry == "latent" else 0)
    pool_layers = (len(spec.attention_layers) if hybrid
                   else dict(spec.page_groups)["full"] if windowed
                   else n_layers)
    if windowed and pool is not None:
        raise NotImplementedError(
            "a provided pool with window layers is not supported: the "
            "engine builds the page store's two groups itself")
    head_dim = (spec.head_dim if spec is not None else 0) or d_model // n_heads
    if pool is not None:
        if kv_dtype != compute_dtype and pool.dtype != kv_dtype:
            raise ValueError(
                f"kv_dtype={jnp.dtype(kv_dtype).name} conflicts with the "
                f"provided pool's dtype {jnp.dtype(pool.dtype).name}")
        if (pool.entry_kind == "latent") != bool(latent):
            raise ValueError(f"the provided pool holds {pool.entry_kind!r} "
                             "entries, the model another kind")
        if pool.n_layers != pool_layers:
            raise ValueError(f"the provided pool has {pool.n_layers} layers, "
                             f"the model {pool_layers} attention layers")
        if sparse and pool.index is None:
            raise ValueError("the model has an indexer: the provided pool "
                             "needs index rows (index_dim=)")
        if mesh is not None and pool.mesh is not mesh:
            raise ValueError("provided pool was built on a different mesh "
                             "than the batcher's")
        kv_dtype, mesh = pool.dtype, pool.mesh
    if hbm is not None and mesh is not None:
        # PR 11's named follow-up, closed as an explicit contract: the
        # elastic pool's grow/shrink per-shard accounting is UNTESTED under
        # a mesh (the ladder recompiles sharded programs per size and
        # concat/slice re-infer the output sharding): reject at
        # construction rather than leave a silent corruption path.
        # ROADMAP item 3 (per-axis ledger) is where this lands properly.
        raise NotImplementedError(
            "HBM-arbiter-armed serving (elastic PagedKVPool) under a "
            "mesh is not supported: grow/shrink per-shard accounting "
            "is untested — serve the arbiter single-device, or the "
            "mesh without an arbiter (hbm=None)")
    n_shards = int(dict(mesh.shape).get("model", 1)) if mesh is not None else 1
    if use_kernel and mesh is not None and n_heads % n_shards:
        raise ValueError(
            f"use_kernel under a mesh needs query heads ({n_heads}) "
            f"divisible by the model axis ({n_shards}) — the ragged "
            "kernel shards the page walk on the heads dim")
    draft = None
    if draft_params is not None:
        from tpulab.models.transformer import weight_shape
        dl = draft_n_layers or n_layers
        dh = draft_n_heads or n_heads
        dkv = draft_n_kv_heads or (n_kv if draft_n_heads is None else dh)
        dd = weight_shape(draft_params["layer0"]["wqkv"])[0]
        if dd // dh != d_model // n_heads or dkv != n_kv:
            raise ValueError(
                "draft model KV geometry (head_dim, n_kv_heads) must "
                "match the target's — both write the shared paged pool")
        if dl > n_layers:
            raise ValueError("draft_n_layers must be <= n_layers (the "
                             "draft shares the pool's layer axis)")
        draft = dict(n_heads=dh, n_layers=dl, n_kv_heads=dkv)

    # The round's token budget comes from the shapes, a power of two at
    # most ``round_ceiling``.  Every width's program must be one a SINGLE
    # prompt reaches, by spending the budget and leaving a tail of that
    # width (how a harness warms them, so that none is first met under
    # load): so twice the budget fits ``max_len``, and the budget a window,
    # where a lane's chunk ends.  With the kernels it is also a round their
    # geometry rule admits
    reach = min(max_len // 2, eva_window or max_len)
    cap = min(round_ceiling, 1 << max(reach, 1).bit_length() - 1)
    why = None if cap == round_ceiling else (
        f"a prompt cannot spend {2 * cap} tokens a round and leave a "
        f"tail: max_len {max_len}"
        + (f", windows of {eva_window}" if eva else ""))
    plan = EnginePlan(
        spec=spec, lanes=lanes, max_len=max_len, page_size=page_size,
        n_heads=n_heads, n_layers=n_layers, rope_theta=rope_theta,
        vocab=vocab, compute_dtype=compute_dtype, kv_dtype=kv_dtype,
        mesh=mesh, n_shards=n_shards,
        state_kind=spec.state_kind if hybrid else None, sparse=sparse,
        eva_window=eva_window,
        eva_saved=spec.eva_window - spec.eva_summaries if eva else 0,
        latent=latent, n_kv=n_kv, head_dim=head_dim, pool_layers=pool_layers,
        window=spec.window if windowed else 0,
        window_layers=dict(spec.page_groups)["window"] if windowed else 0,
        index_dim=spec.index_dim if sparse else 0, max_pages=max_pages,
        prefill_chunk=prefill_chunk, use_kernel=use_kernel, round_cap=cap,
        round_budget_why=why, draft=draft)

    # auto: the pallas ragged kernel on TPU at LONG contexts only (where
    # the gather path's O(lanes*max_len) dense HBM materialization per step
    # should dominate) and only at a geometry the shape rule admits; the
    # XLA gather elsewhere.  No chip measurement backs the threshold yet
    # (ROADMAP S3); explicit use_kernel=True overrides it.  Under a mesh
    # the kernel shards on the KV-heads dim (shard_map), so the auto pick
    # covers sharded serving too.
    auto = use_kernel is None
    if auto:
        use_kernel = (platform.is_tpu() and max_len >= kernel_auto_min_ctx
                      and n_heads % n_shards == 0)
    if use_kernel:
        # the widest round under ``cap`` the rule admits, and what it said
        # of the next wider one; 0 where it admits no width
        admitted, refusal = cap, None
        while admitted and (err := kernel_error(plan, admitted)):
            admitted, refusal = admitted // 2, err
        if admitted:
            cap, why = admitted, refusal or why
        elif auto:
            use_kernel = False
        elif not platform.pallas_interpret():
            # asked for a kernel the geometry cannot have: say which
            # constraint, up front — a Mosaic error past this rule is a
            # real error and propagates.  (The interpreter builds any
            # geometry: a rule that admits no width binds nothing there.)
            raise ValueError(f"use_kernel=True: {refusal}")
    tile = None
    if latent and use_kernel:
        from tpulab.ops.ragged_attention import latent_tile
        widest = round_width(min(prefill_chunk or cap, cap))
        tile = {"round": latent_tile(*_latent_call(plan, widest)),
                "one_row": latent_tile(*_latent_call(plan, 1))}
    return dataclasses.replace(
        plan, use_kernel=bool(use_kernel), round_cap=cap,
        round_budget_why=why, latent_tile=tile)
