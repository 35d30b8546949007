"""The paged step programs: pure functions of (params, page pool, block
tables, tokens) that the scheduler jits.

TPU-first mechanics:
- the page pool is donated through the jitted step, so XLA updates K/V in
  place (no per-token pool copies);
- a step has a *static* shape (fixed lane count B, fixed max pages per
  sequence) — one compiled program regardless of which sessions occupy the
  lanes; inactive lanes are masked, not recompiled;
- attention either gathers pages via the block table (pool[tables] ->
  (B, MP*S, ...), the XLA fallback) or walks them in the pallas ragged
  paged-attention kernel family (tpulab.ops.ragged_attention: per-lane
  (query_len, kv_len) segments serve decode, K+1 verify, and mixed
  chunked-prefill+decode rounds in one program, KV-heads-sharded under
  a mesh — docs/PERFORMANCE.md "Ragged paged attention");
- decode runs K ticks per dispatch (:func:`paged_decode_block`: lax.scan over
  the step, on-device sampling + stop masks), so the host pays one dispatch
  and ONE blocking fetch per K tokens — off-chip the per-token cost is the
  host<->device RTT, and K amortizes it (docs/PERFORMANCE.md);
- a dispatch crosses the host-device boundary once each way: what the host
  knows goes in as ONE int32 buffer and the small results come back as ONE
  (:func:`pack_words` / :func:`unpack_words`, the fields a program in
  :func:`dispatch_fields` and :func:`result_fields`); the page store, a
  block's carry and a round's logits stay on the device.

Every forward runs ONE layer block (:func:`_layer_block`) read from a
:class:`~tpulab.models.spec.ModelSpec`.  For a model with Mamba, Gated
DeltaNet or CCA layers the ``kv_pool`` every step function takes, donates,
carries through its scan and returns is the pair ``(page store, lane
state)``: the attention layers' pages and the per-lane state of the layers
that keep one (:class:`~tpulab.engine.kv_pool.LaneStateStore` ``.arrays``;
a CCA layer keeps both), one pytree;
for a model with a learned indexer it is the pair ``(page store, index
rows)`` (``PagedKVPool.kv`` and ``.index``); for a model with window layers
beside full ones the pair of the page store's two layer groups ``(full,
window)``, each a :class:`~tpulab.engine.kv_pool.PagedKVPool` ``.kv`` with a
table a lane of its own (:func:`_layer_block`).
The functions keep their ``__name__``: a trace names a program
``jit_<name>``, and the benchmark's readers key on it.
:class:`StepPrograms` jits them for one engine plan
(:mod:`tpulab.engine.plan`) through the process-level program memo and is
what the scheduler dispatches through.  Nothing here imports the scheduler
(:mod:`tpulab.engine.paged`).
"""

from __future__ import annotations

import threading
from functools import lru_cache, partial
from typing import Any, Dict, Optional

import numpy as np

from tpulab.engine.kv_pool import kv_rows_view


@lru_cache(maxsize=None)
def _jitted(fn, static=()):
    """``jax.jit(fn)`` made once (this module imports JAX where it runs,
    not where it is imported): a helper that every layer of a program
    calls at one shape is then traced and lowered once."""
    import jax
    return jax.jit(fn, static_argnames=static)


def _scatter_kv(kv_pool, layer, page_idx, slot_idx, knew, vnew):
    """Write new K/V ``(..., Hkv, D)`` at ``(page_idx, slot_idx)`` (both
    shaped ``(...)``) of ``layer``, as rows of the page store: a reshape
    of the new rows, never of the pool.  Callers route what must not land
    to the reserved scratch page 0."""
    knew = kv_rows_view(knew.astype(kv_pool.dtype))
    vnew = kv_rows_view(vnew.astype(kv_pool.dtype))
    kv_pool = kv_pool.at[layer, page_idx, 0, slot_idx].set(knew)
    return kv_pool.at[layer, page_idx, 1, slot_idx].set(vnew)


def _gather_attend(q, k_layer, v_layer, tables, qpos, compute_dtype,
                   window: int = 0):
    """Dense-gather paged attention (the XLA fallback math, single source
    of truth for decode ticks and extend/chunked prefill).

    q (B, M, H, D) query tokens; k_layer/v_layer (P, S, Hkv*D) one
    layer's K and V rows (XLA fuses the slice of the pool into the
    gather); tables (B, MP) page ids; qpos (B, M) global position
    of each query token (visibility: context j attends iff j <= qpos, and
    on a window layer, ``window`` > 0, iff also ``j > qpos - window``: the
    table's entries under the window may be any id, the scratch page's).
    Returns (B, M, H*D).
    """
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import repeat_kv

    b, m, h, d = q.shape
    mp = tables.shape[1]
    page_size = k_layer.shape[1]
    k_ctx = repeat_kv(k_layer[tables].reshape(b, mp * page_size, -1, d), h)
    v_ctx = repeat_kv(v_layer[tables].reshape(b, mp * page_size, -1, d), h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) / np.sqrt(d)
    j = jnp.arange(mp * page_size)
    mask = j[None, None, :] <= qpos[:, :, None]          # (B, M, K)
    if window:
        mask &= j[None, None, :] > qpos[:, :, None] - window
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v_ctx.astype(compute_dtype)).reshape(b, m, h * d)


def _scatter_latent(kv_pool, layer, page_idx, slot_idx, rows):
    """Write latent rows ``(..., W)`` at ``(page_idx, slot_idx)`` of
    ``layer`` of a latent page store, zero-padded to the page row."""
    import jax.numpy as jnp
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, kv_pool.shape[4] - rows.shape[-1])]
    return kv_pool.at[layer, page_idx, 0, slot_idx].set(
        jnp.pad(rows.astype(kv_pool.dtype), pad))


def _gather_attend_latent(q, c_layer, tables, qpos, v_width, sm_scale,
                          compute_dtype):
    """:func:`_gather_attend` for latent pages (absorbed MLA): q (B, M, H,
    W) against one shared key row a position, c_layer (P, S, row >= W);
    the value is the first ``v_width`` columns of the same rows.  Returns
    (B, M, H, v_width)."""
    import jax
    import jax.numpy as jnp

    b, mp = tables.shape
    page_size = c_layer.shape[1]
    ctx = c_layer[tables].reshape(b, mp * page_size, -1)
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32),
                        ctx[..., :q.shape[-1]].astype(jnp.float32)) * sm_scale
    j = jnp.arange(mp * page_size)
    mask = j[None, None, :] <= qpos[:, :, None]          # (B, M, K)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    return jnp.einsum("bhqk,bkc->bqhc", probs,
                      ctx[..., :v_width].astype(compute_dtype))


def _step_spec(spec, d_model: int, n_heads: int, n_layers: int, n_kv_heads,
               rope_theta):
    """The spec a step function runs: the caller's, or the dense decoder's
    from the arguments the step functions always took."""
    from tpulab.models.spec import dense_spec
    return spec or dense_spec(d_model, n_heads, n_layers, n_kv_heads,
                              rope_theta)


def _segment_calls(q, pos, seg):
    """The attention calls of one layer in the SPREAD form, ``(q, q_lens,
    qpos)`` each: what the latent attention takes while its spread is small
    (:data:`SPREAD_LIMIT_BYTES`; the K/V walk takes a round's chunk rows a
    lane at a time, :func:`_kv_walk`).  One,
    as given, in a decode step and in the padded form.  A packed round
    (``seg["rows"]``, see :func:`_layer_block`; q is ``(1, T, ...)``) makes
    one a segment kind, at the width the kind has, since a call computes
    every row of every lane it does not skip: the chunk rows ``[0, M)``
    spread to ``(B, M)`` by one row gather, and the decode rows ``[M, M +
    B)``, which ARE ``(B, 1)``.  Each call's ``q_lens`` are zero for the
    other kind's lanes, which the kernel skips; ``kv_lens`` is the same
    for both (they follow the layer's scatter).  (``jnp.take``, not
    ``x[idx]``: it is jitted, so sixteen layers trace it once.)"""
    import jax.numpy as jnp

    packed = seg.get("rows")
    if packed is None:
        # (``qpos``: the rows the causal mask runs on, where they are not
        # the positions RoPE turns: EVA windows)
        return [(q, seg.get("q_lens"), seg.get("qpos", pos))]
    spread, _back, qpos, chunk_lens, dec_lens = packed
    b, m = qpos.shape
    q = q.reshape(q.shape[1:])                               # (T, ...)
    return [(jnp.take(q, spread, axis=0, mode="clip").reshape(
                (b, m) + q.shape[1:]), chunk_lens, qpos),
            (q[m:, None], dec_lens, seg["kv_lens"][:, None] - 1)]


def _segment_rows(outs, seg):
    """What the calls of :func:`_segment_calls` returned, as the rows the
    layer carries: the one call's output, or ``(1, T, ...)`` with rows ``[0,
    M)`` gathered back out of the chunk call's ``(B, M)`` and rows ``[M, M +
    B)`` the decode call's as they stand.  A row that holds no token reads
    what a skipped lane left unwritten: :func:`_layer_block` zeroes it."""
    import jax.numpy as jnp

    if seg.get("rows") is None:
        return outs[0]
    chunk, dec = outs
    back = seg["rows"][1][:chunk.shape[1]]
    return jnp.concatenate(
        [jnp.take(chunk.reshape((-1,) + chunk.shape[2:]), back, axis=0,
                  mode="clip"), dec[:, 0]])[None]


#: A packed round's chunk rows reach the LATENT attention spread to the
#: padded ``(lanes, M)`` form (:func:`_segment_calls`) while that form is
#: small.  Past this many bytes of spread queries a layer it takes them one
#: chunk LANE at a time (:func:`_chunk_lanes`), as the K/V rows kernel's
#: callers always do (:func:`_kv_walk`): the spread costs what ``lanes x M``
#: rows cost whichever lanes hold a chunk, and at 32 lanes of 64 heads of
#: 576 it was 1.2 GB a layer for one lane's 37 MB, 145 of a 187 ms round
#: with its copies (PERF.md section 6, PR 46).  The configurations under it
#: (``glm4_moe_lite``: 94 MB) keep the programs they had.
SPREAD_LIMIT_BYTES = 256 << 20


def _chunk_lanes(q, seg, tables, attend, width=None):
    """The chunk rows ``q[:M]`` of a packed round (``q (T, ...)``, see
    :func:`_layer_block`) through ``attend`` one chunk LANE at a time, in
    the order the lanes' rows are packed: ``(M, ...)`` rows of what
    ``attend`` returns, shaped and typed as the queries but ``width`` wide
    where that is given (the latent attention's value width).

    ``attend(qq (1, M, ...), tables (1, MP), q_lens (1,), kv_lens (1,),
    qpos (1, M)) -> (1, M, ...)`` is one lane's call, on the lane's cut of
    exactly what the padded ``(B, M)`` call took: its row of ``tables``
    (the layer's own: a window layer's is the window group's), of
    ``seg["kv_lens"]`` and of ``qpos`` (rows of the table, which under EVA
    windows are not positions).  A lane's chunk is consecutive rows from
    its first, so its queries are a slice of ``M`` rows there (``q_lens``
    says how many are its own) and no row is gathered; its result is
    written back over the same rows, and what it leaves behind its own
    rows the next lane's result overwrites, the last lane's falls on rows
    that hold no token.  The loop runs as many times as lanes hold a
    chunk."""
    import jax
    import jax.numpy as jnp

    spread, _back, qpos, chunk_lens, _dec = seg["rows"]
    b, m = qpos.shape
    first = spread.reshape(b, m)[:, 0]           # a lane's first row
    held = chunk_lens > 0
    order = jnp.argsort(jnp.where(held, first, m))
    rows = jnp.pad(q[:m], ((0, m),) + ((0, 0),) * (q.ndim - 1))

    def body(i, out):
        lane, at = order[i], first[order[i]]
        cut = partial(jax.lax.dynamic_slice_in_dim, start_index=lane,
                      slice_size=1)
        one = attend(jax.lax.dynamic_slice_in_dim(rows, at, m)[None],
                     cut(tables), cut(chunk_lens), cut(seg["kv_lens"]),
                     cut(qpos))[0]
        return jax.lax.dynamic_update_slice_in_dim(
            out, one.astype(out.dtype), at, 0)

    out = jax.lax.fori_loop(0, held.sum(), body, jnp.zeros(
        (2 * m,) + q.shape[1:-1] + (width or q.shape[-1],), q.dtype))
    return out[:m]


def _lane_calls(q, seg, tables, attend, width=None):
    """A packed round's attention without the ``(B, M)`` form: the chunk
    rows ``q[0, :M]`` a lane at a time (:func:`_chunk_lanes`) and the
    decode rows ``[M, M + B)`` as the ``(B, 1)`` call they are, as the
    round's rows ``(1, T, ...)`` again."""
    import jax.numpy as jnp

    _spread, _back, qpos, _chunk, dec_lens = seg["rows"]
    return jnp.concatenate([
        _chunk_lanes(q[0], seg, tables, attend, width),
        attend(q[0, qpos.shape[1]:, None], tables, dec_lens, seg["kv_lens"],
               seg["kv_lens"][:, None] - 1)[:, 0]])[None]


def _kv_call(kv_pool, at, seg, compute_dtype, window):
    """ONE call of the attention over layer ``at`` of a K/V page store, by
    the dispatch's path (``seg["use_kernel"]``, ``kernel_geometry``,
    ``mesh``): ``attend(qq (B, M, H, D), tables, q_lens, kv_lens, qpos) ->
    (B, M, H, D)``."""
    import jax.numpy as jnp

    def attend(qq, tables, q_lens, kv_lens, qpos):
        if not seg["use_kernel"]:
            # XLA fallback: gather pages densely then mask
            return _gather_attend(
                qq, kv_pool[at, :, 0], kv_pool[at, :, 1], tables, qpos,
                compute_dtype, window).reshape(qq.shape)
        # pallas ragged kernel: walks block tables page-by-page, no dense
        # gather materialization; fused pages = 1 DMA/page; under a mesh
        # the walk shards on the KV-heads dim via shard_map
        # (tpulab.ops.ragged_attention)
        from tpulab.ops import ragged_attention as ra
        gk, nk = seg["kernel_geometry"] or (None, None)
        if seg["mesh"] is not None:
            return ra.ragged_paged_attention(
                qq, kv_pool, at, tables, q_lens, kv_lens, mesh=seg["mesh"],
                g_pages=gk, nbuf=nk, window=window)
        # the jitted entry itself, not ``ragged_paged_attention`` around
        # it: one Python frame fewer above the kernel
        from tpulab.tpu.platform import pallas_interpret
        return ra._ragged_attn(
            qq, kv_pool, jnp.asarray(at, jnp.int32).reshape(1), tables,
            q_lens, kv_lens, pallas_interpret(), g_pages=gk, nbuf=nk,
            window=window)
    return attend


def _kernel_lane_calls(q, rows, kv_lens, tables, kv_pool, at, geometry,
                       window):
    """:func:`_lane_calls` over the K/V kernels on one device, every
    operand an argument (``at`` a traced scalar) and ``geometry`` and
    ``window`` static: what :func:`_kv_walk` jits, so that the layers of a
    program trace and lower the lane loop ONCE a shape (as they do the
    kernel's jitted entry; inline, the loop cost ~30 ms a layer a program
    of the host's time to trace and lower, half again a dense round's:
    ``setup_s``)."""
    seg = dict(rows=rows, kv_lens=kv_lens, use_kernel=True,
               kernel_geometry=geometry, mesh=None)
    return _lane_calls(q, seg, tables,
                       _kv_call(kv_pool, at, seg, None, window))


def _kv_walk(q, pos, kv_pool, at, tables, seg, compute_dtype, window=0):
    """The walk over a layer's K/V pages, for every caller of the K/V
    kernels (the plain GQA walk of :func:`_layer_block` and
    :func:`_gated_attention`): ``q (B, M, H, D)`` against layer ``at`` of
    ``kv_pool`` under ``tables``, in ``q``'s shape.  A decode step and the
    padded form are ONE call as given.  A packed round (``seg["rows"]``; q
    is ``(1, T, H, D)``) is one call a chunk LANE over its ``M`` rows
    (:func:`_chunk_lanes`: no program writes the ``(B, M)`` form of the
    queries or reads that form of the result) and the decode rows ``[M, M
    + B)``, which ARE ``(B, 1)``, in one call: a lane that holds a chunk
    pays ``M`` query rows, a decoding lane one, an idle lane none.  Both
    forms of a call take this path, the Pallas ragged kernel
    (``seg["use_kernel"]``) and the XLA gather."""
    packed = seg.get("rows")
    if packed is not None and seg["use_kernel"] and seg["mesh"] is None:
        return _jitted(_kernel_lane_calls, ("geometry", "window"))(
            q, packed, seg["kv_lens"], tables, kv_pool, at,
            geometry=seg["kernel_geometry"], window=window)
    attend = _kv_call(kv_pool, at, seg, compute_dtype, window)
    if packed is None:
        # (``qpos``: the rows the causal mask runs on, where they are not
        # the positions RoPE turns: EVA windows)
        return attend(q, tables, seg.get("q_lens"), seg.get("kv_lens"),
                      seg.get("qpos", pos))
    return _lane_calls(q, seg, tables, attend)


def _mla_attention(spec, p, layer, h, pos, kv_pool, page_idx, slot_idx, seg,
                   compute_dtype):
    """Multi-head latent attention of one layer in the absorbed form, on a
    latent page store: ``(attn (B, M, H * v_head_dim), kv_pool)``.  The
    row ``[c_kv ; k_rope]`` (after norm and RoPE) is scattered once; the
    key up-projection moves into the query, the value up-projection
    behind the weighted latent sum.  In a packed round (``seg["rows"]``,
    see :func:`_layer_block`) ``h`` is ``(1, T, D)``: only the absorbed
    query goes through :func:`_segment_calls` for the walk over the pages,
    and the weighted latent sum is rows again before ``w_uv``; where the
    round's padded ``(lanes, M)`` form of that query would pass
    ``SPREAD_LIMIT_BYTES`` its chunk rows go a lane at a time instead
    (:func:`_chunk_lanes`)."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _rmsnorm, apply_rope, qmat

    with jax.named_scope("mla_attention"):
        eps = spec.rms_eps
        b, m = h.shape[:2]
        nope, rope = spec.qk_nope_head_dim, spec.qk_rope_head_dim
        scale = 1.0 / np.sqrt(spec.qk_head_dim)
        cq = _rmsnorm(h @ qmat(p["wq_a"], compute_dtype),
                      p["q_norm"]["scale"], eps)
        q = (cq @ qmat(p["wq_b"], compute_dtype)).reshape(
            b, m, spec.n_heads, nope + rope)
        kva = h @ qmat(p["wkv_a"], compute_dtype)
        ckv = _rmsnorm(kva[..., :spec.kv_lora_rank], p["kv_norm"]["scale"],
                       eps)
        # (YaRN's frequencies where the spec scales them, else theta's)
        inv = spec.rope_inv_freq()
        kr = apply_rope(kva[..., None, spec.kv_lora_rank:], pos,
                        spec.rope_theta, inv)[..., 0, :]
        qr = apply_rope(q[..., nope:], pos, spec.rope_theta, inv)
        rows = jnp.concatenate([ckv, kr], axis=-1)           # (B, M, W)
        kv_pool = _scatter_latent(
            kv_pool, layer, page_idx, slot_idx,
            rows.reshape(page_idx.shape + rows.shape[-1:]))
        qa = jnp.concatenate(
            [jnp.einsum("bmhn,hnc->bmhc", q[..., :nope],
                        qmat(p["w_uk"], compute_dtype)), qr], axis=-1)

        def attend(qq, tables, q_lens, kv_lens, qpos):    # one call
            if seg["use_kernel"]:
                from tpulab.ops.ragged_attention import (
                    ragged_latent_attention)
                return ragged_latent_attention(
                    qq, kv_pool, layer, tables, q_lens, kv_lens,
                    v_width=spec.kv_lora_rank, sm_scale=scale)
            return _gather_attend_latent(
                qq, kv_pool[layer, :, 0], tables, qpos, spec.kv_lora_rank,
                scale, compute_dtype)
        packed = seg.get("rows")
        if packed is not None and (packed[2].size * spec.n_heads * qa.shape[-1]
                                   * qa.dtype.itemsize > SPREAD_LIMIT_BYTES):
            # a wide round: the chunk rows a lane at a time, the decode
            # rows [M, M + B) as the (B, 1) call they are
            lat = _lane_calls(qa, seg, seg["tables"], attend,
                              spec.kv_lora_rank)
        else:
            lat = _segment_rows([
                attend(qq, seg["tables"], q_lens, seg.get("kv_lens"), qpos)
                for qq, q_lens, qpos in _segment_calls(qa, pos, seg)],
                seg)                                         # (b, m, H, C)
        attn = jnp.einsum("bmhc,hcv->bmhv", lat.astype(compute_dtype),
                          qmat(p["w_uv"], compute_dtype))
        return attn.reshape(b, m, -1), kv_pool


def _sparse_attention(spec, p, layer, h, pos, valid, kv_pool, page_idx,
                      slot_idx, seg, compute_dtype):
    """GQA attention of one layer over the keys a learned indexer selects
    (``spec.index_topk``; :mod:`tpulab.ops.sparse_attention`), on the pair
    ``kv_pool = (page store, index rows)``: ``(attn (B, M, H * D),
    kv_pool)``.

    Projections, per-head QK-norm, RoPE; the new K/V rows and the new index
    keys (LayerNorm, RoPE over their own width) are scattered under the
    same ``(page_idx, slot_idx)``; then, on ROWS — every query token of the
    dispatch with its lane, whatever the form (a decode step's ``B``, a
    packed round's ``T``, the padded form's ``B * M``) — the indexer scores
    each row against every key of its lane at or before it, the
    ``index_topk`` largest are selected (all of them while the context is
    no longer than that: plain causal GQA), and the row attends to those
    alone.  A packed round (``seg["rows"]``) makes the three calls once a
    segment kind, chunk rows ``[0, M)`` and decode rows ``[M, M + B)``, as
    :func:`_segment_calls` does and for its reason: a call walks a lane's
    pages for all the rows it is given."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import (_rmsnorm, apply_rope, qmat,
                                           split_qkv)
    from tpulab.ops import sparse_attention as sa

    pages, index = kv_pool
    b, m = h.shape[:2]
    f32 = jnp.float32
    eps, theta = spec.rms_eps, spec.rope_theta
    q, knew, vnew = split_qkv(h @ qmat(p["wqkv"], compute_dtype), b, m,
                              spec.n_heads, spec.n_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = _rmsnorm(q, p["q_norm"]["scale"], eps)
        knew = _rmsnorm(knew, p["k_norm"]["scale"], eps)
    if theta:
        q, knew = apply_rope(q, pos, theta), apply_rope(knew, pos, theta)
    tail = knew.shape[2:]
    pages = _scatter_kv(pages, layer, page_idx, slot_idx,
                        knew.reshape(page_idx.shape + tail),
                        vnew.reshape(page_idx.shape + tail))
    use_kernel, tables, kv_lens = (seg["use_kernel"], seg["tables"],
                                   seg["kv_lens"])
    with jax.named_scope("dsa_indexer"):
        ix = p["indexer"]
        hi, di = spec.index_heads, spec.index_dim
        a = (h @ qmat(ix["wq"], compute_dtype)).reshape(b, m, hi, di)
        key = (h @ qmat(ix["wk"], compute_dtype)).astype(f32)
        key = key - key.mean(-1, keepdims=True)
        key = (key * jax.lax.rsqrt(jnp.square(key).mean(-1, keepdims=True)
                                   + eps) * ix["k_norm"]["scale"].astype(f32)
               + ix["k_norm"]["bias"].astype(f32)).astype(compute_dtype)
        if theta:
            a = apply_rope(a, pos, theta)
            key = apply_rope(key[..., None, :], pos, theta)[..., 0, :]
        c = jnp.dot(h, qmat(ix["ww"], compute_dtype),
                    preferred_element_type=f32) * (hi * di) ** -0.5
        key = jnp.pad(key.astype(index.dtype).reshape(
            page_idx.shape + (di,)),
            [(0, 0)] * page_idx.ndim + [(0, index.shape[3] - di)])
        index = index.at[layer, page_idx, slot_idx].set(key)
        # gather-after-scatter, by whole pages: (B, W, row).  (Out of the
        # whole store by ``layer * P + page`` the gather is a ``jnp.take``
        # with a fill: 2.8 ms a decode step where this form, a slice of
        # the layer's rows and then the gather, is 1.3: PR 34's chip runs)
        ictx = index[layer][tables].reshape(tables.shape[0], -1,
                                            index.shape[3])
    w = ictx.shape[1]
    packed = seg.get("rows")
    row_lane = (seg["row_seg"][0] if packed is not None else jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32)[:, None], (b, m)).reshape(-1))
    row_lane = jnp.where(valid.reshape(-1), row_lane, -1)
    row_pos = pos.reshape(-1)
    q = q.reshape((b * m,) + q.shape[2:])
    a, c = a.reshape(b * m, hi, di), c.reshape(b * m, hi)
    # (rows, the lanes that hold one, whether row b is lane b's one row)
    if packed is not None:
        cut = packed[2].shape[1]                      # M: chunk | decode rows
        calls = [(slice(0, cut), packed[3] > 0, False),
                 (slice(cut, None), packed[4] > 0, True)]
    else:
        calls = [(slice(None), valid.any(axis=1), m == 1)]
    outs = []
    for rows, lane_live, one_a_lane in calls:
        with jax.named_scope("dsa_indexer"):
            scores = sa.index_scores(a[rows], c[rows], row_lane[rows], ictx,
                                     lane_live, kv_lens, use_kernel)
        with jax.named_scope("dsa_select"):
            live = ((jnp.arange(w)[None, :] <= row_pos[rows, None])
                    & (row_lane[rows, None] >= 0))
            chosen = sa.select_topk(scores, live, spec.index_topk)
        with jax.named_scope("dsa_attention"):
            if use_kernel and one_a_lane:
                outs.append(sa.sparse_attend_decode(
                    q[rows], chosen, pages, layer, tables, lane_live,
                    kv_lens))
                continue
            outs.append(sa.sparse_attend(
                q[rows], chosen, row_lane[rows], pages, layer, tables,
                lane_live, kv_lens, compute_dtype, use_kernel))
    attn = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    return attn.astype(compute_dtype).reshape(b, m, -1), (pages, index)


def _pages(kv_pool):
    """The page store of a step function's ``kv_pool``: itself, or the
    first of the pair a model with a lane state (``(pages, lane state)``)
    or with an indexer (``(pages, index rows)``) is served with."""
    return kv_pool[0] if isinstance(kv_pool, tuple) else kv_pool


def _windowed(spec) -> bool:
    """Whether ``spec`` (None: the dense decoder) has window layers: its
    page store is then two groups and a dispatch carries a table each."""
    return spec is not None and bool(spec.window)


def _row_lens(spec, kv_lens):
    """``kv_lens`` (positions a lane holds after the dispatch) as the rows
    its table holds then: itself, or with EVA windows the row behind the
    last position, plus one (:meth:`ModelSpec.cache_row`; no segment
    crosses a window's end, so a segment's rows are consecutive too)."""
    if not spec.eva_window:
        return kv_lens
    import jax.numpy as jnp
    return jnp.where(kv_lens > 0, spec.cache_row(kv_lens - 1) + 1, 0)


def _segment_window(x, k, tails, seg, live=None, fresh=None):
    """The row gather of a lane-state layer's causal window: the last ``k``
    inputs of every row of ``x (rows, C)``, from the rows themselves and the
    lanes' kept tails ``tails (k - 1, lanes, C)`` (one layer of the store):
    ``(window (k, rows, C)``, oldest first, ``window[k - 1]`` is ``x``; the
    lanes' new tails, shaped and typed as ``tails``)``.  What
    :func:`_segment_conv` weighs a channel, and what CCA's grouped taps
    multiply a head and its shifted value reads one token back
    (:func:`_cca_qkv`).

    The one rule of :func:`_mamba_mixer`, for the window: a segment at
    position 0 starts from a zero tail whatever the slot holds, any other
    from the slot; the new tail is the last ``k - 1`` inputs of ``[tail ;
    segment]``; rows without a token and dead or idle lanes write nothing.
    A decode step (no ``seg["row_seg"]``: row b is lane b's one token;
    ``live`` the lanes that run, ``fresh`` those at position 0) shifts the
    lane's window by one.  A packed round takes row t's input ``back``
    tokens back from row ``t - back`` of its segment where the row's offset
    reaches that far, else from the lane's tail."""
    import jax.numpy as jnp

    rows = seg.get("row_seg")
    if rows is None:
        tail = jnp.where(fresh[None, :, None], 0, tails)
        window = jnp.concatenate([tail, x[None]], axis=0)      # (k, B, C)
        new_tail = jnp.where(live[None, :, None], window[1:], tails)
    else:
        row_lane, row_off = rows
        q_lens, kv_lens = seg["q_lens"], seg["kv_lens"]
        b, t = q_lens.shape[0], x.shape[0]
        lane = jnp.maximum(row_lane, 0)
        fresh = (q_lens > 0) & (kv_lens == q_lens)
        tail = jnp.where(fresh[None, :, None], 0, tails)
        window = [x]
        for back in range(1, k):
            # the input ``back`` tokens back: a row of this round, or
            # what the lane kept of the rounds before
            kept = tail[jnp.clip(row_off - back + k - 1, 0, k - 2), lane]
            window.insert(0, jnp.where(
                (row_off >= back)[:, None],
                jnp.pad(x, ((back, 0), (0, 0)))[:t], kept))
        window = jnp.stack(window)
        # the lane's new tail: the last k - 1 of [tail ; segment]
        spread = seg["rows"][0]
        s = q_lens[:, None] + jnp.arange(k - 1)[None, :] - (k - 1)
        from_rows = x[spread[jnp.arange(b)[:, None] * (spread.shape[0] // b)
                             + jnp.maximum(s, 0)]]             # (B, k-1, C)
        kept = jnp.take_along_axis(
            tail, jnp.clip(s + k - 1, 0, k - 2).T[:, :, None], axis=0)
        new_tail = jnp.where((s >= 0).T[:, :, None],
                             from_rows.transpose(1, 0, 2), kept)
    return window, new_tail.astype(tails.dtype)


def _segment_conv(x, w, conv, at, seg, live=None, fresh=None):
    """The depthwise causal convolution of a lane-state mixer over the rows
    ``x (rows, C)`` with taps ``w (k, C)`` (tap ``j`` weighs the input ``k -
    1 - j`` tokens back), from the lanes' kept tails ``conv[at] (k - 1,
    lanes, C)`` (:func:`_segment_window`, whose rule it follows): ``(acc
    (rows, C) float32 before bias and activation, conv with layer at's new
    tails)``."""
    import jax.numpy as jnp

    window, tails = _segment_window(x, w.shape[0], conv[at], seg, live, fresh)
    acc = (window.astype(jnp.float32) * w[:, None, :]).sum(0)
    return acc, conv.at[at].set(tails)


def _mamba_mixer(spec, p, at, h, pos, valid, state, seg, compute_dtype):
    """The Mamba-1 mixer of one layer (Jamba's: a state a CHANNEL, ``(d_state,
    d_inner)``, ``dt`` through a low-rank projection, RMSNorm on dt, B and C;
    the Mamba-2 mixer, a state a HEAD under one decay, is
    :func:`_mamba2_mixer`) over the lane state ``state = (ssm, conv)``, whose
    layer ``at`` it reads and writes: ``(out, state)``, ``out`` shaped like
    ``h``.

    One rule for both forms: a segment that starts at position 0 starts
    from zeros, whatever the lane's slot holds; any other segment starts
    from the slot; the slot is written from the segment's last valid row;
    rows without a token and lanes that are dead or idle write nothing.
    So a reused lane, a preempted request that prefills again from 0 and a
    resume need no reset.

    A decode step (``h (B, 1, D)``, ``valid (B, 1)`` the live lanes) is the
    one-token recurrence in XLA.  A packed round (``seg["row_seg"]``: ``h
    (1, T, D)``) scans the segments in
    :func:`tpulab.ops.selective_scan.selective_scan`.  The convolution of
    both forms is :func:`_segment_conv`."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _rmsnorm, qmat

    f32 = jnp.float32
    ssm, conv = state
    din, n, r = spec.d_inner, spec.d_state, spec.dt_rank
    rows = seg.get("row_seg")
    if rows is None and h.shape[1] != 1:
        raise NotImplementedError(
            "Mamba layers run in a decode step or a packed round "
            "(paged_mixed_step), not in the padded (B, M) form")
    with jax.named_scope("mamba_proj"):
        uz = (h @ qmat(p["in_proj"], compute_dtype)).reshape(-1, 2 * din)
        x, z = uz[:, :din], uz[:, din:]              # (rows, din)
    live = fresh = None
    with jax.named_scope("mamba_conv"):
        w = p["conv_w"].astype(f32)
        if rows is None:
            live, fresh = valid[:, 0], pos[:, 0] == 0
        acc, conv = _segment_conv(x, w, conv, at, seg, live, fresh)
        u = jax.nn.silu(acc + p["conv_b"].astype(f32)).astype(compute_dtype)
    with jax.named_scope("mamba_proj"):
        eps = spec.rms_eps
        xp = u @ qmat(p["x_proj"], compute_dtype)
        bb = _rmsnorm(xp[:, r:r + n], p["b_norm"]["scale"], eps).astype(f32)
        cc = _rmsnorm(xp[:, r + n:], p["c_norm"]["scale"], eps).astype(f32)
        dt = jax.nn.softplus(
            (_rmsnorm(xp[:, :r], p["dt_norm"]["scale"], eps)
             @ qmat(p["dt_proj"], compute_dtype)).astype(f32)
            + p["dt_bias"].astype(f32))
    with jax.named_scope("mamba_scan"):
        a = -jnp.exp(p["a_log"].astype(f32))                  # (N, din)
        d = p["d"].astype(f32)
        uf = u.astype(f32)
        if rows is None:
            h0 = jnp.where(fresh[:, None, None], 0.0, ssm[at])
            hn = (jnp.exp(dt[:, None, :] * a[None]) * h0
                  + (dt * uf)[:, None, :] * bb[:, :, None])
            y = (hn * cc[:, :, None]).sum(1) + d * uf
            ssm = ssm.at[at].set(
                jnp.where(live[:, None, None], hn, ssm[at]))
        else:
            from tpulab.ops.selective_scan import row_flags, selective_scan
            row_lane, row_off = rows
            y, ssm = selective_scan(
                uf, dt, bb, cc, a, d, ssm, at, row_lane,
                row_flags(row_lane, row_off, seg["q_lens"], seg["kv_lens"]),
                use_kernel=seg["use_kernel"])
    with jax.named_scope("mamba_out"):
        out = ((y * jax.nn.silu(z.astype(f32))).astype(compute_dtype)
               @ qmat(p["out_proj"], compute_dtype))
    return out.reshape(h.shape), (ssm, conv)


def _gdn_mixer(spec, p, at, h, pos, valid, state, seg, compute_dtype):
    """The Gated DeltaNet mixer of one layer (Qwen3-Next's) over the lane
    state ``state = (ssm, conv)``, whose layer ``at`` it reads and writes:
    ``(out, state)``, ``out`` shaped like ``h``.  ``ssm[at]`` is ``(lanes,
    value heads, d_k, d_v)`` float32, ``conv[at]`` the last ``d_conv - 1``
    inputs of the convolution over the ``[q | k | v]`` channels.

    :func:`_mamba_mixer`'s one rule, in a function of its own (so that the
    Mamba hybrids' programs and call paths are what they were): a segment
    at position 0 starts from zeros whatever the slot holds, any other from
    the slot; the slot is written from the segment's last valid row; rows
    without a token and dead or idle lanes write nothing.

    A decode step (``h (B, 1, D)``) is the one-token delta rule on the
    store (:func:`tpulab.ops.gated_delta_rule.one_token_gated_delta_rule`).
    A packed round (``seg["row_seg"]``: ``h (1, T, D)``) takes the
    convolution's taps across segment starts by :func:`_segment_conv`; its
    chunk rows ``[0, M)`` run the chunk kernel ``chunk_gated_delta_rule``
    and its decode rows ``[M, M + B)``, one a lane, the one-token kernel
    ``gated_delta_step`` (different lanes, so in either order); each kernel
    moves the slots of the lanes that hold a row and no other.  Without
    kernels (``seg["use_kernel"]`` false) a decode step is the XLA form of
    the one-token rule and every row of a round goes through the sequential
    form."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _rmsnorm, qmat
    from tpulab.ops import gated_delta_rule as gdr

    f32 = jnp.float32
    ssm, conv = state
    hk, hv, dk, dv = (spec.gdn_k_heads, spec.gdn_v_heads, spec.gdn_k_dim,
                      spec.gdn_v_dim)
    nk = hk * dk
    rows = seg.get("row_seg")
    if rows is None and h.shape[1] != 1:
        raise NotImplementedError(
            "Gated DeltaNet layers run in a decode step or a packed round "
            "(paged_mixed_step), not in the padded (B, M) form")
    n = h.shape[0] * h.shape[1]
    with jax.named_scope("gdn_proj"):
        qkvz = (h @ qmat(p["in_qkvz"], compute_dtype)).reshape(n, -1)
        ba = (h @ qmat(p["in_ba"], compute_dtype)).astype(f32).reshape(n, -1)
        x, z = qkvz[:, :2 * nk + hv * dv], qkvz[:, 2 * nk + hv * dv:].reshape(
            n, hv, dv)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            ba[:, hv:] + p["dt_bias"].astype(f32))
    live = fresh = None
    with jax.named_scope("gdn_conv"):
        w = p["conv_w"].astype(f32)
        if rows is None:
            live, fresh = valid[:, 0], pos[:, 0] == 0
        acc, conv = _segment_conv(x, w, conv, at, seg, live, fresh)
        u = jax.nn.silu(acc)

        def unit(t):               # a head's q or k over its L2 norm
            t = t.reshape(n, hk, dk)
            return t * jax.lax.rsqrt(jnp.square(t).sum(-1, keepdims=True)
                                     + 1e-6)
        qh, kh, v = unit(u[:, :nk]) * dk ** -0.5, unit(u[:, nk:2 * nk]), \
            u[:, 2 * nk:]
    with jax.named_scope("gdn_rule"):
        kernel = seg["use_kernel"]
        if rows is None:
            o, ssm = gdr.one_token_gated_delta_rule(
                qh.reshape(n, nk), kh.reshape(n, nk), v, g, beta, ssm, at,
                live, fresh, use_kernel=kernel)
        else:
            from tpulab.ops.selective_scan import ROW_ZERO, row_flags
            row_lane, row_off = rows
            flags = row_flags(row_lane, row_off, seg["q_lens"],
                              seg["kv_lens"])
            # the chunk kernel's rows: all but the lanes' decode rows
            m = n - seg["q_lens"].shape[0] if kernel else n
            o, ssm = gdr.chunk_gated_delta_rule(
                qh[:m].reshape(m, nk), kh[:m].reshape(m, nk), v[:m], g[:m],
                beta[:m], ssm, at, row_lane[:m], flags[:m],
                use_kernel=kernel)
            if m < n:
                # a row without a token reads what the kernel never wrote,
                # which may not be a number (and 0 x that is not 0 where an
                # attention layer reads the scratch page it scatters to)
                o = jnp.where((row_lane[:m] >= 0)[:, None], o, 0.0)
                o_dec, ssm = gdr.one_token_gated_delta_rule(
                    qh[m:].reshape(n - m, nk), kh[m:].reshape(n - m, nk),
                    v[m:], g[m:], beta[m:], ssm, at, row_lane[m:] >= 0,
                    (flags[m:] & ROW_ZERO) != 0, use_kernel=kernel)
                o = jnp.concatenate([o, o_dec])
    with jax.named_scope("gdn_out"):
        o = _rmsnorm(o.reshape(n, hv, dv), p["norm"]["scale"].astype(f32),
                     spec.rms_eps) * jax.nn.silu(z.astype(f32))
        out = (o.reshape(n, hv * dv).astype(compute_dtype)
               @ qmat(p["out_proj"], compute_dtype))
    return out.reshape(h.shape), (ssm, conv)


def _mamba2_in_proj(spec, p, h, compute_dtype):
    """A Mamba-2 layer's in-projection of the rows ``h``: ``(z, xBC, dt)``,
    ``(rows, d_inner)``, ``(rows, conv_dim)`` and ``(rows, heads)``, the
    column blocks of ONE product with the served leaf ``in_proj`` in the
    published order ``[z | xBC | dt]``.

    The three are read far apart (``xBC`` by the convolution and the tail's
    write, ``dt`` by the recurrence, ``z`` by the gate behind it), and in the
    52-layer round XLA would rather compute the whole product again for each
    reader than keep 11 MB of it: four products a layer, each at the bf16
    peak (PR 61: 13.9 of a 64.5 ms round; no shallower program shows it).
    The barrier makes the three blocks values of their own, so the product
    has ONE reader, beside it, and is computed once; what it costs is the
    blocks written and read once more."""
    import jax
    from tpulab.models.transformer import qmat

    din, cd = spec.m2_heads * spec.m2_head_dim, spec.m2_conv_dim
    zxd = (h @ qmat(p["in_proj"], compute_dtype)).reshape(
        h.shape[0] * h.shape[1], -1)
    return jax.lax.optimization_barrier(
        (zxd[:, :din], zxd[:, din:din + cd], zxd[:, din + cd:]))


def _mamba2_mixer(spec, p, at, h, pos, valid, state, seg, compute_dtype):
    """The Mamba-2 mixer of one layer (Nemotron-H's: a scalar decay a head,
    ``B`` and ``C`` shared by a group of heads, a gated group norm; Mamba-1
    is :func:`_mamba_mixer`) over the lane state ``state = (ssm, conv)``,
    whose layer ``at`` it reads and writes: ``(out, state)``, ``out`` shaped
    like ``h``.  ``ssm[at]`` is ``(lanes, heads, head_dim, state)`` float32,
    ``conv[at]`` the last ``d_conv - 1`` inputs of the convolution over the
    ``[x | B | C]`` channels together.

    :func:`_mamba_mixer`'s one rule, in a function of its own: a segment at
    position 0 starts from zeros whatever the slot holds, any other from
    the slot; the slot is written from the segment's last valid row; rows
    without a token and dead or idle lanes write nothing.

    A decode step (``h (B, 1, D)``) is the one-token recurrence on the
    store (:func:`tpulab.ops.ssd.one_token_ssd`).  A packed round
    (``seg["row_seg"]``: ``h (1, T, D)``) takes the convolution's taps
    across segment starts by :func:`_segment_conv`; its chunk rows ``[0,
    M)`` run the chunked form (:func:`tpulab.ops.ssd.chunk_ssd`: whole
    chunks of ``spec.m2_chunk`` rows as matrix products, the state carried
    in float32 from chunk to chunk and, in the slot, from round to round)
    and its decode rows ``[M, M + B)``, one a lane, the one-token form
    (different lanes, so in either order).  The chunked form is XLA in
    either plan; the one-token form is the kernel ``ssd_step`` with the
    kernels (``seg["use_kernel"]``: it moves the slots of the lanes that
    hold a row and no other, once each way), else its XLA definition."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import qmat
    from tpulab.ops import ssd

    f32 = jnp.float32
    ssm, conv = state
    nh, hd, g, ns = (spec.m2_heads, spec.m2_head_dim, spec.m2_groups,
                     spec.m2_state)
    din = nh * hd
    rows = seg.get("row_seg")
    if rows is None and h.shape[1] != 1:
        raise NotImplementedError(
            "Mamba-2 layers run in a decode step or a packed round "
            "(paged_mixed_step), not in the padded (B, M) form")
    n = h.shape[0] * h.shape[1]
    with jax.named_scope("mamba2_proj"):
        z, xbc, dt = _mamba2_in_proj(spec, p, h, compute_dtype)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    live = fresh = None
    with jax.named_scope("mamba2_conv"):
        if rows is None:
            live, fresh = valid[:, 0], pos[:, 0] == 0
        acc, conv = _segment_conv(xbc, p["conv_w"].astype(f32), conv, at,
                                  seg, live, fresh)
        u = jax.nn.silu(acc + p["conv_b"].astype(f32))
    with jax.named_scope("mamba2_ssd"):
        x = u[:, :din].reshape(n, nh, hd)
        b = u[:, din:din + g * ns].reshape(n, g, ns)
        c = u[:, din + g * ns:].reshape(n, g, ns)
        a, d = -jnp.exp(p["a_log"].astype(f32)), p["d"].astype(f32)
        if rows is None:
            y, ssm = ssd.one_token_ssd(x, dt, a, b, c, d, ssm, at, live,
                                       fresh, use_kernel=seg["use_kernel"])
        else:
            from tpulab.ops.selective_scan import ROW_ZERO, row_flags
            row_lane, row_off = rows
            flags = row_flags(row_lane, row_off, seg["q_lens"],
                              seg["kv_lens"])
            m = n - seg["q_lens"].shape[0]         # the chunk rows
            y, ssm = ssd.chunk_ssd(
                x[:m], dt[:m], a, b[:m], c[:m], d, ssm,
                jnp.asarray(at, jnp.int32), row_lane[:m], flags[:m],
                chunk=spec.m2_chunk)
            y_dec, ssm = ssd.one_token_ssd(
                x[m:], dt[m:], a, b[m:], c[m:], d, ssm, at,
                row_lane[m:] >= 0, (flags[m:] & ROW_ZERO) != 0,
                use_kernel=seg["use_kernel"])
            y = jnp.concatenate([y, y_dec])
    with jax.named_scope("mamba2_norm"):
        # the gate BEFORE the norm, the norm over a group's channels
        y = (y.reshape(n, din) * jax.nn.silu(z.astype(f32))).reshape(
            n, g, din // g)
        y = (y * jax.lax.rsqrt(jnp.square(y).mean(-1, keepdims=True)
                               + spec.rms_eps)).reshape(n, din) * p[
                                   "norm"]["scale"].astype(f32)
    with jax.named_scope("mamba2_out"):
        out = y.astype(compute_dtype) @ qmat(p["out_proj"], compute_dtype)
    return out.reshape(h.shape), (ssm, conv)


#: the mixers that keep a lane state and no pages, by ``ModelSpec.mixers``
#: name, which is also the name of the layer's leaves
_LANE_MIXERS = {"mamba": _mamba_mixer, "gdn": _gdn_mixer,
                "mamba2": _mamba2_mixer}


def _cca_qkv(spec, p, at, h, pos, valid, state, seg, compute_dtype):
    """What compressed convolutional attention (ZAYA1's CCA, ``p`` the
    layer's ``cca`` leaves) hands the dense decoder's K/V walk, and the lane
    state ``state = (c tails, a tails, shifted value)`` with layer ``at``'s
    new tails: ``(q (B, M, H, D), k, v (B, M, KV, D), state)``.
    :func:`_layer_block` scatters the rows into the lane's pages and attends
    as it does for plain GQA (scale ``D^-0.5``), so a CCA layer reads and
    writes layer ``at`` of BOTH stores.

    On ROWS (a decode step's ``B``, a packed round's ``T``): ``c = [q~ ;
    k~]`` and the value's two halves from one product; the depthwise taps
    over ``c`` and the grouped taps (a ``head_dim x head_dim`` block a head a
    tap) over their output ``a``, each from its own window of the rows and
    the lane's tail (:func:`_segment_window`: ``a`` is rounded to the
    compute type before it is windowed, so that a row reads the same ``a``
    of the token before it from the round as from the tail); the q-k mean of
    the PRE-convolution ``c`` added to both; each head's L2 norm in float32,
    ``q`` times ``sqrt(D)`` and ``k`` times ``tau sqrt(D)``; RoPE over the
    first ``rotary_dim`` columns; ``v = [h_t W_v1 ; h_(t-1) W_v2]``, the
    second half read through the same window, cut into the KV heads in that
    order.  :func:`_mamba_mixer`'s one rule holds for the three tails, so a
    reused lane, a re-prefill and a resume need no reset.  The padded ``(B,
    M)`` form is refused as a lane state's is."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import apply_rope, qmat

    f32 = jnp.float32
    hq, hkv, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    nq, nc, rep = hq * d, (hq + hkv) * d, hq // hkv
    rows = seg.get("row_seg")
    if rows is None and h.shape[1] != 1:
        raise NotImplementedError(
            "CCA layers run in a decode step or a packed round "
            "(paged_mixed_step), not in the padded (B, M) form")
    n = h.shape[0] * h.shape[1]
    live = fresh = None
    if rows is None:
        live, fresh = valid[:, 0], pos[:, 0] == 0
    with jax.named_scope("cca_proj"):
        proj = (h @ qmat(p["in_proj"], compute_dtype)).reshape(n, -1)
        c, v1, v2 = (proj[:, :nc], proj[:, nc:nc + hkv * d // 2],
                     proj[:, nc + hkv * d // 2:])
    with jax.named_scope("cca_mix"):
        tail_c, tail_a, tail_v = state
        acc, tail_c = _segment_conv(c, p["conv0_w"].astype(f32), tail_c, at,
                                    seg, live, fresh)
        a = (acc + p["conv0_b"].astype(f32)).astype(compute_dtype)
        win, tails = _segment_window(a, spec.cca_taps[1], tail_a[at], seg,
                                     live, fresh)
        tail_a = tail_a.at[at].set(tails)
        conv = jnp.einsum(
            "jngd,jgde->nge", win.reshape(win.shape[:2] + (hq + hkv, d)),
            qmat(p["conv1_w"], compute_dtype),
            preferred_element_type=f32) + p["conv1_b"].astype(f32).reshape(
                hq + hkv, d)
        # the q-k mean, from the values before the convolutions: a query
        # head with its KV head's key, a KV head with its query heads' mean
        qt = c[:, :nq].astype(f32).reshape(n, hkv, rep, d)
        kt = c[:, nq:].astype(f32).reshape(n, hkv, 1, d)
        q = conv[:, :hq].reshape(n, hkv, rep, d) + (qt + kt) / 2
        k = conv[:, hq:].reshape(n, hkv, 1, d) + (
            qt.mean(2, keepdims=True) + kt) / 2

        def unit(t):               # a head over its L2 norm, times sqrt(D)
            return t * jax.lax.rsqrt(jnp.maximum(
                jnp.square(t).sum(-1, keepdims=True), 1e-24)) * d ** 0.5
        q = unit(q).reshape(h.shape[:2] + (hq, d))
        k = (unit(k) * p["tau"].astype(f32).reshape(hkv, 1, 1)).reshape(
            h.shape[:2] + (hkv, d))
        if spec.rope_theta:
            rot = spec.rotary_dim or d
            q, k = (jnp.concatenate(
                [apply_rope(t[..., :rot], pos, spec.rope_theta),
                 t[..., rot:]], axis=-1) for t in (q, k))
        win, tails = _segment_window(v2, 2, tail_v[at], seg, live, fresh)
        tail_v = tail_v.at[at].set(tails)
        v = jnp.concatenate([v1, win[0]], axis=-1).reshape(k.shape)
    return q.astype(compute_dtype), k, v, (tail_c, tail_a, tail_v)


def _residual(spec, p, name, x, y):
    """A sublayer's output ``y`` joined to the residual ``x``: ``x + y``, or
    with residual scaling (``spec.res_scale``; ``p[name]`` the sublayer's
    four vectors ``s_r b_r s_o b_o``, ``name`` ``"res_attn"`` or
    ``"res_ffn"``) ``s_r (x + b_r) + s_o (y + b_o)``, in float32, held in
    the residual's dtype."""
    import jax
    import jax.numpy as jnp

    if not spec.res_scale:
        return x + y.astype(x.dtype)
    p = p[name]
    with jax.named_scope("res_scale"):
        f32 = jnp.float32
        return (p["s_r"].astype(f32) * (x.astype(f32) + p["b_r"].astype(f32))
                + p["s_o"].astype(f32) * (y.astype(f32)
                                          + p["b_o"].astype(f32))
                ).astype(x.dtype)


def _gated_attention(spec, p, layer, h, pos, kv_pool, page_idx, slot_idx,
                     seg, compute_dtype):
    """GQA attention of one layer with an output gate (``spec.attn_gate``:
    ``wqkv`` = ``[q | k | v]``, a query head's columns ``[query | gate]``),
    RMSNorm over each head of q and k and RoPE over the first
    ``spec.rotary_dim`` columns of a head: ``(attn * sigmoid(gate) (B, M, H
    * D), kv_pool)`` on ``"kv"`` pages.  The walk over the pages is the
    dense decoder's (:func:`_kv_walk`)."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _rmsnorm, apply_rope, qmat

    b, m = h.shape[:2]
    at = spec.store_layer(layer)
    hq, hkv, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    qkv = h @ qmat(p["wqkv"], compute_dtype)
    qg = qkv[..., :2 * hq * d].reshape(b, m, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    knew = qkv[..., 2 * hq * d:(2 * hq + hkv) * d].reshape(b, m, hkv, d)
    vnew = qkv[..., (2 * hq + hkv) * d:].reshape(b, m, hkv, d)
    if spec.qk_norm:
        q = _rmsnorm(q, p["q_norm"]["scale"], spec.rms_eps)
        knew = _rmsnorm(knew, p["k_norm"]["scale"], spec.rms_eps)
    if spec.rope_theta:
        rot = spec.rotary_dim or d
        q, knew = (jnp.concatenate(
            [apply_rope(t[..., :rot], pos, spec.rope_theta), t[..., rot:]],
            axis=-1) for t in (q, knew))
    tail = knew.shape[2:]
    kv_pool = _scatter_kv(kv_pool, at, page_idx, slot_idx,
                          knew.reshape(page_idx.shape + tail),
                          vnew.reshape(page_idx.shape + tail))
    attn = _kv_walk(q, pos, kv_pool, at, seg["tables"], seg,
                    compute_dtype).astype(compute_dtype)
    with jax.named_scope("attn_gate"):
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            compute_dtype)
    return attn.reshape(b, m, -1), kv_pool


def _mhc_pre(spec, p, x):
    """The READ side of one sublayer's hyper-connection (mHC; ``p`` is the
    sublayer's ``hc_attn`` or ``hc_ffn``): from the streams ``x (B, M, n,
    D)`` the row the sublayer reads and the maps it writes back through,
    ``(u (B, M, D), h_res (B, M, n, n), h_post (B, M, n))``, a set a token::

        v      = RMSNorm_g(vec(X))             over all n D values, hc_eps
        h_pre  = sigmoid(a_pre (v phi_pre) + b_pre)
        h_post = 2 sigmoid(a_post (v phi_post) + b_post)
        S      = clip(a_res mat(v phi_res) + b_res, hc_clamp)
        H_res  = exp(S), then hc_sinkhorn_iters times: rows over (their
                 sum + hc_eps), columns over (their sum + hc_eps)
        u      = sum_i h_pre[i] X[i]

    The streams are held in their own dtype; ``v``, the projections, the
    sweeps and ``u``'s sum are float32 (the projection at full precision:
    the TPU's default would round ``v`` and ``phi`` to bf16), and the maps
    stay float32 for :func:`_mhc_post`.  The sums over the ``n`` streams
    are written out term by term: element-wise work XLA fuses, where an
    einsum over a width of 4 becomes a batch of tiny matrix products."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc_pre"):
        f32, n = jnp.float32, spec.hc_mult
        b, m = x.shape[:2]
        xf = x.astype(f32)
        flat = xf.reshape(b, m, -1)
        v = (flat * jax.lax.rsqrt(jnp.square(flat).mean(-1, keepdims=True)
                                  + spec.hc_eps)
             * p["norm"]["scale"].astype(f32))
        proj = jnp.einsum("bmk,kj->bmj", v, p["phi"].astype(f32),
                          precision=jax.lax.Precision.HIGHEST)
        alpha, bias = p["alpha"].astype(f32), p["bias"].astype(f32)
        h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                      + bias[n:2 * n])
        h_res = jnp.exp(jnp.clip(alpha[2] * proj[..., 2 * n:] + bias[2 * n:],
                                 *spec.hc_clamp)).reshape(b, m, n, n)
        for _ in range(spec.hc_sinkhorn_iters):
            h_res = h_res / (h_res.sum(-1, keepdims=True) + spec.hc_eps)
            h_res = h_res / (h_res.sum(-2, keepdims=True) + spec.hc_eps)
        u = sum(h_pre[..., i, None] * xf[..., i, :] for i in range(n))
        return u.astype(x.dtype), h_res, h_post


def _mhc_post(x, h_res, h_post, f):
    """The WRITE side of one sublayer's hyper-connection: ``X' = H_res X +
    outer(h_post, f)`` from the streams ``x (B, M, n, D)`` the sublayer
    read, :func:`_mhc_pre`'s maps and the sublayer's output ``f (B, M,
    D)``; accumulated in float32, held in the streams' dtype."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc_post"):
        xf, n = x.astype(jnp.float32), x.shape[-2]
        mixed = sum(h_res[..., :, j, None] * xf[..., None, j, :]
                    for j in range(n))
        return (mixed + h_post[..., None] * f.astype(jnp.float32)[
            ..., None, :]).astype(x.dtype)


def _streams(spec, x, out: bool = False):
    """The step functions' two ends of the hyper-connected residual: the
    embedding rows ``x (..., D)`` widened to ``hc_mult`` equal streams
    ``(..., n, D)``, or (``out``) the streams after the last layer summed
    (in float32) for the final norm.  ``x`` itself where the spec has the
    plain residual."""
    import jax.numpy as jnp

    if not spec.hc_mult:
        return x
    if out:
        return x.astype(jnp.float32).sum(-2)
    return jnp.broadcast_to(x[..., None, :],
                            x.shape[:-1] + (spec.hc_mult, x.shape[-1]))


def _ffn_block(spec, p, layer, x, valid, compute_dtype, shortcut=None,
               routed=None):
    """``x + ffn(norm(x))`` of one layer: the dense FFN, or the routed
    experts (router kind ``spec.router``; of the router's ``E`` experts the
    share ``spec.expert_first`` / ``spec.experts_held`` whose weights are
    here) plus the shared expert where the model has one, scaled by its
    sigmoid gate where it has that (``spec.shared_gate``).  Returns ``(x,
    stats)``, ``stats`` the expert layer's ``(E + 2,)`` counters or None.
    A layer of kind ``"none"`` (its mixer is all it is) returns ``x`` as it
    came.  The experts' form is ``spec.expert_act``: SwiGLU, or ``relu2``
    (``down(relu(up x)^2)``, the shared expert in the same form).

    A ``"shortcut"`` layer (LongCat-Flash) runs BOTH on the one normed
    input: its ``x`` is ``x + dense(h)`` and the expert block's output ``m``
    (the held experts' part and the identity columns' ``weight * h``) goes
    out beside it, ``((x, m), stats)``, to be added after the NEXT layer's
    FFN, which is handed it as ``shortcut``.

    With hyper-connections (``spec.hc_mult``) ``x`` is the streams ``(B, M,
    n, D)``: the FFN reads the row :func:`_mhc_pre` makes of them (the
    layer's ``hc_ffn``) and :func:`_mhc_post` writes its output back.

    With residual scaling (``spec.res_scale``) the sum is :func:`_residual`'s
    with the layer's ``res_ffn``.  With the ``"mlp"`` router the expert
    layer returns ``((x, state), stats)``: ``state`` is what its router
    hands to the next layer's (depth averaging), which is handed it as
    ``routed`` (None on the first expert layer)."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _dense_ffn, _rmsnorm

    kind = spec.layer_kinds[layer]
    if kind == "none":      # the layer is its mixer alone: no norm, no
        return x, None      # residual and no product here
    streams = None
    if spec.hc_mult:
        streams, (x, *maps) = x, _mhc_pre(spec, p["hc_ffn"], x)
    h = _rmsnorm(x, p["ln2"]["scale"], spec.rms_eps)
    if kind == "dense":
        if streams is not None:
            return _mhc_post(streams, *maps,
                             _dense_ffn(p, h, compute_dtype)), None
        x = _residual(spec, p, "res_ffn", x, _dense_ffn(p, h, compute_dtype))
        if shortcut is not None:
            with jax.named_scope("moe_shortcut"):
                x = x + shortcut
        return x, None
    from tpulab.parallel.moe import routed_ffn
    b, m = x.shape[:2]
    y, stats, *state = routed_ffn(
        p["moe"], h.reshape(b * m, -1), spec.top_k, compute_dtype,
        router=spec.router, act=spec.expert_act, scale=spec.routed_scale,
        norm=spec.norm_topk, valid=valid.reshape(-1),
        first=spec.expert_first, held=spec.experts_held or None,
        zero=spec.zero_experts,
        prev=routed, eps=spec.rms_eps)
    y = y.reshape(b, m, -1)
    if kind == "shortcut":
        return (x + _dense_ffn(p, h, compute_dtype).astype(x.dtype),
                y.astype(x.dtype)), stats
    if spec.shared_gate:
        from tpulab.models.transformer import qmat
        with jax.named_scope("moe_shared"):
            y = y + _dense_ffn(p["shared"], h, compute_dtype) * jax.nn.sigmoid(
                (h @ qmat(p["shared"]["gate"], compute_dtype)).astype(
                    jnp.float32))
    elif spec.n_shared and spec.expert_act == "relu2":
        from tpulab.models.transformer import qmat
        with jax.named_scope("moe_shared"):
            y = y + jnp.square(jax.nn.relu(
                h @ qmat(p["shared"]["w1"], compute_dtype))) @ qmat(
                    p["shared"]["w2"], compute_dtype)
    elif spec.n_shared:
        with jax.named_scope("moe_shared"):
            y = y + _dense_ffn(p["shared"], h, compute_dtype)
    if streams is not None:
        return _mhc_post(streams, *maps, y), stats
    x = _residual(spec, p, "res_ffn", x, y)
    return ((x, *state) if state else x), stats


def _layer_block(spec, p, layer, x, pos, valid, kv_pool, page_idx, slot_idx,
                 seg, compute_dtype):
    """ONE decoder layer over paged state, for every model and every step
    function: norm, projections (+RoPE), the new rows scattered into the
    lane's pages, attention over the block table (gather-after-scatter,
    global causality), output projection, norm, FFN; residuals around both
    halves.

    x (B, M, D) at positions ``pos`` (B, M); ``page_idx``/``slot_idx`` are
    the write targets, shaped (B, M) — or (B,) in a decode step, whose one
    row a lane is then written without the M axis; rows that must not land
    go to scratch page 0.  ``seg`` is the dispatch's segment description,
    the same for every layer: ``tables`` (B, MP), ``q_lens``/``kv_lens``
    (B,), and the attention path (``use_kernel``: the Pallas ragged kernel
    of the cache-entry kind, else the XLA gather; ``kernel_geometry``,
    ``mesh``).  ``valid`` (B, M) bool says which rows hold a token: the
    others take zeros out of the kernel path's attention (a lane the kernel
    skips leaves its rows unwritten) and are left out of the expert counters.

    Three forms, told apart by what ``seg`` carries.  A decode step is
    (B, 1).  The padded form is (B, M), lane b's segment left-packed in
    row b (K+1 verify, where every lane's segment has one length).  A
    packed round (:func:`paged_mixed_step`) carries ``seg["rows"]``: x is
    (1, T, D), one row a token of the round, and everything but the walk
    over the pages runs on those T rows; ``rows = (spread (B * M,), back
    (T,), qpos (B, M), chunk_lens (B,), decode_lens (B,))`` holds the row
    behind each slot of the (B, M) form, the slot behind each row, and
    ``q_lens`` split by segment kind.  The attention is called once a
    chunk LANE and once for the decode rows (:func:`_kv_walk`; the latent
    and the sparse attention once a segment kind, :func:`_segment_calls`):
    a lane that holds a chunk pays M query rows, a decoding lane one, an
    idle lane none, where one call at (B, M) made every lane pay M (PR 33:
    65 of a round's 79 ms at 8 lanes) and the (B, M) form of the queries
    alone a fifth of a round at 32 lanes (PR 59).
    Returns ``(x, kv_pool, stats)``: ``stats`` is the expert layer's
    ``(E + 2,)`` int32 counters
    (:func:`tpulab.parallel.moe.routing_stats`) or None on a dense layer.

    With an indexer (``spec.index_topk``) ``kv_pool`` is the pair ``(page
    store, index rows)`` and the attention reads only the keys the indexer
    selects (:func:`_sparse_attention`).  With window layers beside full
    ones (``spec.window``, ``spec.attn_kinds``; Mellum2's) it is the pair of
    the page store's two groups ``(full, window)``, each ``(L_g, P_g) +
    kv_page_shape``, and ``seg`` carries the window group's table and write
    targets (``wtables``, ``wpage_idx``, read from it as ``page_idx`` is
    from ``tables``; ``slot_idx`` is the position's slot in either): the
    plain GQA walk below scatters into and walks the layer's OWN group at
    its index there.  A window layer sees the ``spec.window`` keys that end
    at the row: its table holds live ids from the block of the lane's
    oldest visible key on, and the walk and the mask take the window as
    their lower bound (:func:`tpulab.ops.ragged_attention._ragged_attn`
    ``window=``; :func:`_gather_attend`).  Both kinds norm each head of q
    and k (``spec.qk_norm``); a full layer turns them by YaRN's table with
    its factor on cos and sin, a window layer by ``theta^(-2j / d)``.

    The layer's mixer is attention over the pages (above; with an output
    gate and partial RoPE, :func:`_gated_attention`) or, by ``spec.mixers``,
    a Mamba-1 block (:func:`_mamba_mixer`), a Mamba-2 block
    (:func:`_mamba2_mixer`) or a Gated DeltaNet block (:func:`_gdn_mixer`)
    over the lane state; ``kv_pool`` is then the pair ``(page store, lane
    state)`` for every layer of the model, and only attention layers own a
    layer of the page store (``spec.store_layer``).  A layer may also be ONE
    sublayer (Nemotron-H's): a mixer alone (``spec.layer_kinds`` ``"none"``:
    :func:`_ffn_block` hands ``x`` back) or a feed-forward part alone
    (``spec.mixers`` ``"none"``: nothing above this line runs), one norm and
    one residual either way.

    A ``"shortcut"`` layer (``spec.layer_kinds``; LongCat-Flash's
    shortcut-connected expert block) returns its ``x`` as the pair ``(x,
    m)``: the residual stream and the expert block's output, computed from
    this layer's FFN input and due after the NEXT layer's FFN.  The step
    functions hand the pair on as they hand ``x`` on, and the next layer
    (always ``"dense"``) takes it apart again (:func:`_ffn_block`).

    With hyper-connections (``spec.hc_mult``; Xing4.0's mHC) ``x`` is a
    token's ``n`` residual streams, ``(B, M, n, D)`` in every form (``(1,
    T, n, D)`` in a packed round): each of the layer's two sublayers reads
    ONE row a token, a weighted sum of the streams (:func:`_mhc_pre`; the
    layer's ``hc_attn`` here, ``hc_ffn`` in :func:`_ffn_block`), and its
    output goes back through a doubly stochastic ``n x n`` matrix and an
    ``n``-vector a token (:func:`_mhc_post`) where the plain residual adds
    it.  The step functions widen the embedding into the streams and sum
    them before the final norm (:func:`_streams`).

    A CCA layer (``spec.cca_taps``; ZAYA1's compressed convolutional
    attention) owns a layer of BOTH stores: ``kv_pool`` is the pair ``(page
    store, lane state)`` as a hybrid's is; :func:`_cca_qkv` makes ``q``,
    ``k`` and ``v`` from the lane's tails and writes the new ones into the
    second, and the plain GQA walk below scatters the rows into the first
    and attends.  With residual scaling (``spec.res_scale``) both
    sublayers join the residual through :func:`_residual`.  With the
    ``"mlp"`` router every layer but the last returns its ``x`` as the pair
    ``(x, router state)`` and the next layer takes it apart, as a
    ``"shortcut"`` layer's pair travels: the state is a function of the
    token, handed on inside the program and never stored.

    Kept short, the mixers and the K/V walk (:func:`_kv_walk`) in
    functions of their own.  What the host spends tracing and lowering
    these lines it spends once a layer a program, and that is ``setup_s``:
    on the v5e host, tracing a kernel body cost more with every Python
    frame between the step function and the ``pallas_call`` (PR 28, my
    chip runs: a kernel's trace took 0.63 s a program with the parent's
    frames, 0.87-0.97 s behind one more, 1.42 s behind four more and a
    helper inside the kernel; the dense cell's set-up grew 10 %, 96 -> 106
    s, until the count was the parent's again; PR 35 found the cause in
    CPython's frame chunks, ``core/threads.on_one_frame_chunk``), and a
    loop written out in every layer is traced and lowered in every layer
    (PR 59: :func:`_kernel_lane_calls`).
    """
    from contextlib import nullcontext

    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import (_rmsnorm, apply_rope, qmat,
                                           split_qkv)

    if spec.mixers[layer] == "none":
        # the layer is its feed-forward part alone (ONE norm, ONE residual)
        x, stats = _ffn_block(spec, p, layer, x, valid, compute_dtype)
        return x, kv_pool, stats
    shortcut = streams = routed = None
    if layer and spec.layer_kinds[layer - 1] == "shortcut":
        x, shortcut = x
    if layer and spec.router == "mlp":
        x, routed = x
    if spec.hc_mult:
        streams, (x, *maps) = x, _mhc_pre(spec, p["hc_attn"], x)
    h = _rmsnorm(x, p["ln1"]["scale"], spec.rms_eps)
    state = None
    if spec.state_kind:
        # a hybrid's store is the pair (pages, lane state): a layer whose
        # mixer keeps a lane state (Mamba-1, Gated DeltaNet, Mamba-2) reads
        # and writes the second alone, an attention layer the first (a CCA
        # layer both, below)
        kv_pool, state = kv_pool
        name = spec.mixers[layer]
        if name in _LANE_MIXERS:
            mixed, state = _LANE_MIXERS[name](
                spec, p[name], spec.store_layer(layer), h, pos, valid, state,
                seg, compute_dtype)
            x, stats = _ffn_block(spec, p, layer, x + mixed, valid,
                                  compute_dtype)
            return x, (kv_pool, state), stats
    if spec.attention == "mla":
        attn, kv_pool = _mla_attention(spec, p, layer, h, pos, kv_pool,
                                       page_idx, slot_idx, seg,
                                       compute_dtype)
    elif spec.index_topk:
        attn, kv_pool = _sparse_attention(spec, p, layer, h, pos, valid,
                                          kv_pool, page_idx, slot_idx, seg,
                                          compute_dtype)
    elif spec.attn_gate:
        attn, kv_pool = _gated_attention(spec, p, layer, h, pos, kv_pool,
                                         page_idx, slot_idx, seg,
                                         compute_dtype)
    else:
        b, m = x.shape[:2]
        at = spec.store_layer(layer)       # its layer of the page store
        # with window layers beside full ones the store is two groups, each
        # with a table a lane: the layer scatters into and walks ITS group
        # (``window`` keys at most, 0: every key), the other passes through
        window, tables, other = spec.layer_window(layer), seg["tables"], None
        if spec.window:
            full, win = kv_pool
            kv_pool, other = (win, full) if window else (full, win)
            if window:
                tables, page_idx = seg["wtables"], seg["wpage_idx"]
        if spec.cca_taps:
            # ... and of the lane state: q, k and v from its convolutions
            q, knew, vnew, state = _cca_qkv(spec, p["cca"], at, h, pos,
                                            valid, state, seg, compute_dtype)
        else:
            q, knew, vnew = split_qkv(h @ qmat(p["wqkv"], compute_dtype), b,
                                      m, spec.n_heads, spec.n_kv_heads,
                                      spec.head_dim)
            if spec.qk_norm:
                q = _rmsnorm(q, p["q_norm"]["scale"], spec.rms_eps)
                knew = _rmsnorm(knew, p["k_norm"]["scale"], spec.rms_eps)
            if spec.rope_scaling and not window:
                # a FULL layer under YaRN: its frequencies, and its factor
                # on cos and sin (so q . k carries the factor's square), in
                # float32 before the cast; a window layer turns by theta
                inv, f = spec.rope_inv_freq(), spec.rope_factor
                q, knew = ((apply_rope(t.astype(jnp.float32), pos,
                                       inv_freq=inv) * f).astype(t.dtype)
                           for t in (q, knew))
            elif spec.rope_theta:
                q = apply_rope(q, pos, spec.rope_theta)
                knew = apply_rope(knew, pos, spec.rope_theta)
        tail = knew.shape[2:]
        kv_pool = _scatter_kv(kv_pool, at, page_idx, slot_idx,
                              knew.reshape(page_idx.shape + tail),
                              vnew.reshape(page_idx.shape + tail))
        attn = _kv_walk(q, pos, kv_pool, at, tables, seg, compute_dtype,
                        window).astype(compute_dtype)
        attn = attn.reshape(b, m, -1)
        if other is not None:
            kv_pool = (other, kv_pool) if window else (kv_pool, other)
    if seg["use_kernel"]:
        # rows that hold no token take zeros: the kernel leaves the block of
        # a lane it skips unwritten, and what that holds may not be a number.
        # (The gather path computes every row, and the dense golden holds its
        # padded form to the parent's bits in the rows without a token too.)
        attn = jnp.where(valid[..., None], attn, 0)
    with jax.named_scope("cca_out") if spec.cca_taps else nullcontext():
        attn = attn @ qmat(p["wo"], compute_dtype)
    x, stats = _ffn_block(
        spec, p, layer,
        (_residual(spec, p, "res_attn", x, attn) if streams is None
         else _mhc_post(streams, *maps, attn)),
        valid, compute_dtype, shortcut, routed)
    if spec.router == "mlp" and layer == spec.n_layers - 1:
        x = x[0]                # nobody reads the last router's state
    return x, (kv_pool if state is None else (kv_pool, state)), stats


# -- one buffer each way ------------------------------------------------------
# A transfer costs the same 0.5-0.7 ms whatever it carries (PERF.md, PR 36),
# so a dispatch makes one in and one out.  A buffer is int32 words: float32
# and uint32 travel as their bits, bool as 0/1, so every value a program
# computes with is the value the host wrote.  ``fields`` is the static
# description both sides build from the same numbers: ``(name, dtype,
# shape)`` in the buffer's order; the last field's shape may hold one -1,
# which takes what is left of the buffer (the one width a program is keyed
# by: a block's stop ids a lane, a round's rows).
_I32, _F32, _U32, _BOOL = (np.dtype(t) for t in (np.int32, np.float32,
                                                 np.uint32, np.bool_))


def _bitcast(x, dtype):
    """``x`` as ``dtype`` of the same width, bit for bit: a view of a numpy
    array, ``bitcast_convert_type`` of a jax array."""
    if x.dtype == dtype:
        return x
    if isinstance(x, np.ndarray):
        return x.view(dtype)
    import jax
    return jax.lax.bitcast_convert_type(x, dtype)


def pack_words(fields, arrays: Dict[str, Any]):
    """``arrays`` (by field name; numpy arrays on the host, jax arrays in a
    program) as ONE 1-D int32 buffer, each field's words one after the
    other.  Every array must have its field's dtype and shape."""
    if set(arrays) != {name for name, _, _ in fields}:
        raise ValueError(f"fields {[f[0] for f in fields]} != arrays "
                         f"{sorted(arrays)}")
    parts = []
    for name, dtype, shape in fields:
        a = arrays[name]
        if a.dtype != dtype or len(shape) != a.ndim or any(
                s not in (-1, n) for s, n in zip(shape, a.shape)):
            raise ValueError(f"field {name}: {a.dtype}{tuple(a.shape)} is "
                             f"not {dtype}{tuple(shape)}")
        a = a.astype(np.int32) if dtype == _BOOL else _bitcast(a, np.int32)
        parts.append(a.reshape(-1))
    if all(isinstance(a, np.ndarray) for a in parts):
        return np.concatenate(parts)
    import jax.numpy as jnp
    return jnp.concatenate(parts)


def unpack_words(fields, buf) -> Dict[str, Any]:
    """The inverse of :func:`pack_words`: ``{name: array}`` out of a 1-D
    int32 buffer, by static slices (numpy in, numpy out; in a program,
    jax arrays)."""
    sizes = [int(np.prod([n for n in shape if n >= 0]))
             for _name, _dtype, shape in fields]
    open_end = -1 in fields[-1][2]
    left = buf.shape[0] - sum(sizes[:-1] if open_end else sizes)
    if left < 0 or (left % sizes[-1] if open_end else left):
        raise ValueError(f"a buffer of {buf.shape[0]} words does not fit "
                         f"fields of {sizes} words")
    if open_end:
        sizes[-1] = left
    out, at = {}, 0
    for (name, dtype, shape), n in zip(fields, sizes):
        words = buf[at:at + n]
        at += n
        words = words != 0 if dtype == _BOOL else _bitcast(words, dtype)
        out[name] = words.reshape(shape)
    return out


#: stop ids a lane in a round's buffer.  A round's ONE open width is its
#: rows, so its stop ids have a width of their own; a lane with more of them
#: takes no carry through a round (the scheduler fetches such a round before
#: it plans the next dispatch: chain break ``stops``)
ROUND_STOPS = 8


def dispatch_fields(program: str, lanes: int, max_pages: int,
                    window: bool = False):
    """What the host sends with a dispatch of ``program``: ``"tick"``
    (:func:`paged_decode_step_sampled`), ``"block"``
    (:func:`paged_decode_block`; ``fresh`` says, a lane, whether lengths,
    tokens, active and rem count or the carry's), ``"spec"``
    (:func:`paged_speculative_block`) or ``"round"``
    (:func:`paged_mixed_step`; ``rows`` stacks :func:`pack_round`'s
    ``toks``, ``row_lane``, ``row_off``; ``fresh`` says, a lane, whether
    its decode row's token, ``kv_lens`` and ``rem`` count or the carry's;
    ``rem`` is the tokens a lane that emits in this round still wants, this
    round's included, and 0 for a lane in mid-prompt; ``stops`` its stop
    ids padded with -1, :data:`ROUND_STOPS` wide).  ``window``: the model
    has window layers, and behind ``tables`` (the full group's) goes
    ``wtables``, the window group's table a lane, as wide: entry ``p //
    page_size`` is position ``p``'s page in either, the window group's live
    from the block of the lane's oldest visible key on."""
    b = (lanes,)
    table = (("tables", _I32, (lanes, max_pages)),) + (
        (("wtables", _I32, (lanes, max_pages)),) if window else ())
    sampling = (("temps", _F32, b), ("seeds", _U32, (lanes, 2)))
    if program == "round":
        return table + (("q_lens", _I32, b), ("kv_lens", _I32, b)) \
            + sampling + (("fresh", _BOOL, b), ("rem", _I32, b),
                          ("stops", _I32, (lanes, ROUND_STOPS)),
                          ("rows", _I32, (3, -1)))
    fields = table + (("lengths", _I32, b), ("tokens", _I32, b),
                      ("active", _BOOL, b)) + sampling
    if program == "tick":
        return fields
    extra = {"block": ("fresh", _BOOL, b),
             "spec": ("draft_tables", _I32, (lanes, max_pages))}[program]
    return fields + (extra, ("rem", _I32, b), ("stops", _I32, (lanes, -1)))


def result_fields(lanes: int, k: Optional[int] = None, moe=None,
                  spec: bool = False):
    """What a dispatch brings back: a pick a lane (``k`` None: a tick, a
    round) or ``k`` picks a lane with their prefix mask (a block; a
    speculative block also its ``drafted`` and ``accepted`` a lane), and
    the expert layers' counters where the model has any (``moe``: their
    shape ``(n_moe, E + 2)``, see :func:`moe_shape`)."""
    shape = (lanes,) if k is None else (lanes, k)
    fields = (("tokens", _I32, shape), ("logprobs", _F32, shape))
    if k is not None:
        fields += (("emitted", _BOOL, shape),)
    if spec:
        fields += (("drafted", _I32, (lanes,)), ("accepted", _I32, (lanes,)))
    if moe is not None:
        fields += (("moe", _I32, tuple(moe)),)
    return fields


def moe_shape(spec):
    """The shape of a dispatch's expert counters, None for a model without
    expert layers (``spec`` may be None: the dense decoder)."""
    if spec is None or not spec.moe_layers:
        return None
    return (len(spec.moe_layers), spec.n_experts + 2)


def _pack_results(lanes, k, moe, spec=False, **arrays):
    """A program's small results as one array (:func:`result_fields`);
    ``moe`` is the list a step returns, empty without expert layers."""
    if moe:
        arrays["moe"] = moe[0]
    return pack_words(
        result_fields(lanes, k, moe[0].shape if moe else None, spec), arrays)


def paged_decode_step(params, kv_pool, tables, lengths, tokens,
                      active, n_heads: int, n_layers: int,
                      compute_dtype, use_kernel: bool = False,
                      n_kv_heads: Optional[int] = None,
                      rope_theta: Optional[float] = None,
                      temps=None, seeds=None,
                      kernel_geometry: Optional[tuple] = None,
                      mesh=None, spec=None, wtables=None):
    """One batched decode tick over the paged pool.

    Shapes: kv_pool (L, P, 2, S, Hkv*D) fused page store (axis 2 = K/V,
    :func:`kv_page_shape`),
    tables (B, MP) int32 page ids (padded rows repeat page 0),
    lengths (B,) current position per lane, tokens (B,), active (B,) bool.
    Returns (logits (B, vocab), kv_pool) — the pool donated by the caller.
    Under GQA (``n_kv_heads < n_heads``) the pool holds ``n_kv_heads``
    heads per slot.

    With ``temps (B,) f32`` + ``seeds (B, 2) uint32`` the return becomes
    (next_tokens (B,) i32, logprobs (B,) f32, logits, kv_pool): lanes
    with temp > 0 are Gumbel-max temperature-sampled ON DEVICE with a key
    folded from (seed, position) — batch-composition- and
    preemption-invariant — and temp == 0 lanes take the argmax;
    ``logprobs`` is each lane's chosen-token log-probability
    (log-softmax at the chosen id).  Callers then fetch only (B,)-sized
    arrays (no per-tick (B, vocab) logits transfer).

    For a ``spec`` with Mamba layers ``kv_pool`` is the pair ``(page store,
    lane state)``, in and out; a lane that is not ``active`` holds its
    state as it routes its K/V to the scratch page.  For a ``spec`` with
    window layers it is the pair of the page store's groups ``(full,
    window)`` and ``wtables (B, MP)`` the window group's table a lane
    (:func:`_layer_block`).
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    b = tokens.shape[0]
    page_size = _pages(kv_pool).shape[3]
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens][:, None, :]
    spec = _step_spec(spec, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    x = _streams(spec, x)
    # write target per lane: page id + slot for position `lengths`;
    # inactive/padded lanes are routed to the RESERVED scratch page 0 so
    # they can never clobber a live lane's pages
    # (the ROW of the table that holds the position: the position itself,
    # or behind the summaries of its EVA windows, ModelSpec.cache_row)
    row = spec.cache_row(lengths)
    page_idx = tables[jnp.arange(b), row // page_size]          # (B,)
    safe_page = jnp.where(active, page_idx, 0)
    safe_slot = jnp.where(active, row % page_size, 0)
    # the ragged kernel at the q=1 decode shape; per-lane positions: each
    # lane decodes at its own length
    pos = lengths[:, None]
    seg = dict(tables=tables, q_lens=jnp.ones_like(lengths),
               kv_lens=row + 1, use_kernel=use_kernel,
               kernel_geometry=kernel_geometry, mesh=mesh)
    if spec.eva_window:
        seg["qpos"] = row[:, None]
    if spec.window:
        seg.update(wtables=wtables, wpage_idx=jnp.where(
            active, wtables[jnp.arange(b), row // page_size], 0))
    moe_stats = []
    for layer in range(spec.n_layers):
        x, kv_pool, stats = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos, active[:, None],
            kv_pool, safe_page, safe_slot, seg, compute_dtype)
        if stats is not None:
            moe_stats.append(stats)

    x = _rmsnorm(_streams(spec, x, out=True), params["final_norm"]["scale"],
                 spec.rms_eps)
    logits = _lm_head(params, x[:, 0])
    # inactive lanes emit neutral logits (argmax 0) — callers mask on active
    logits = jnp.where(active[:, None], logits, 0.0)
    # an expert model's counters ride behind the pool (one small array)
    moe = (jnp.stack(moe_stats),) if moe_stats else ()
    if temps is None:
        return (logits, kv_pool) + moe
    import jax
    next_tokens = jax.vmap(_device_sample_token)(
        logits, temps, seeds.astype(jnp.uint32), lengths)
    logp_rows = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    logprobs = jnp.take_along_axis(logp_rows, next_tokens[:, None],
                                   axis=-1)[:, 0]
    return (next_tokens, logprobs, logits, kv_pool) + moe


def paged_decode_step_sampled(params, kv_pool, packed, lanes: int,
                              max_pages: int, **kw):
    """The scheduler's K=1 tick: :func:`paged_decode_step` with device
    sampling armed (a greedy lane's pick is the argmax), on ONE packed
    buffer (:func:`dispatch_fields` ``"tick"``).  Returns ``(results,
    logits (B, vocab), kv_pool)``: ``results`` is :func:`result_fields`
    ``(lanes, moe=...)`` packed, ``logits`` stays on the device unless a
    host-sampled lane fetches its row."""
    f = unpack_words(dispatch_fields("tick", lanes, max_pages,
                                     _windowed(kw.get("spec"))), packed)
    if "wtables" in f:
        kw = dict(kw, wtables=f["wtables"])
    nt, lp, logits, kv_pool, *moe = paged_decode_step(
        params, kv_pool, f["tables"], f["lengths"], f["tokens"], f["active"],
        temps=f["temps"], seeds=f["seeds"], **kw)
    return (_pack_results(lanes, None, moe, tokens=nt, logprobs=lp), logits,
            kv_pool)


def paged_decode_block(params, kv_pool, packed, carry, lanes: int,
                       max_pages: int, n_heads: int, n_layers: int,
                       compute_dtype,
                       k: int = 8, use_kernel: bool = False,
                       n_kv_heads: Optional[int] = None,
                       rope_theta: Optional[float] = None,
                       kernel_geometry: Optional[tuple] = None,
                       mesh=None, spec=None):
    """K fused decode ticks in ONE dispatch: ``lax.scan`` over
    :func:`paged_decode_step`, sampling every step on device.

    The per-token serving cost off-chip is dominated by the host<->device
    round trip (dispatch + blocking fetch), not the decode math — chaining
    K steps inside one compiled program amortizes that RTT over K tokens
    (the host then syncs once per K tokens instead of once per token, the
    fused multi-token decode shape of TPU-native serving stacks).

    Per-lane device-side stop mask: a lane is *live* while it is active,
    has steps remaining, and has not emitted a stop token.  ``steps_rem
    (B,) i32`` counts tokens still wanted per lane; ``stop_ids (B, S)
    i32`` holds each lane's stop-token ids padded with -1 (token ids are
    always >= 0, so the pad never matches).  A stop token IS emitted as
    the lane's final token (matching the host-side contract), then the
    lane goes dead for the rest of the block: its K/V writes route to the
    reserved scratch page and its position stops advancing — which also
    keeps the (seed, position)-folded device-sampling stream identical to
    a K=1 run.

    The CALLER pre-allocates pages: step j writes K/V at ``lengths + j``
    for live lanes, so ``tables`` must already cover every position the
    block can reach.

    What the host knows arrives as ONE buffer, ``packed``
    (:func:`dispatch_fields` ``"block"``: ``tables``, ``lengths``,
    ``tokens``, ``active``, ``temps``, ``seeds``, ``fresh``, ``rem``,
    ``stops``).  ``carry = (lengths, tokens, live, steps_rem)`` is what the
    block before this one returned: a lane takes its state from there
    unless the buffer says ``fresh`` (a chain's first block is fresh in
    every lane and passes any carry of the right shapes), so both are the
    same compiled program.

    Returns ``(results, lengths (B,), last_tokens (B,), live (B,),
    steps_rem (B,), kv_pool)``.  ``results`` is ONE int32 array,
    :func:`result_fields` ``(lanes, k, moe)``: ``tokens (B, K)``,
    ``logprobs (B, K)`` as their bits, ``emitted (B, K)`` and, for a
    ``spec`` with expert layers, their counters ``(n_moe, E + 2)`` summed
    over the K steps; ``lengths`` .. ``steps_rem`` and the pool are the
    carried state *after* the block, returned as device arrays so a
    follow-up block can be dispatched without a host round trip
    (dispatch-ahead overlap).  ``emitted[b]`` is a prefix mask: lane b's
    valid tokens are ``tokens[b, :emitted[b].sum()]``.
    """
    import jax
    import jax.numpy as jnp

    f = unpack_words(dispatch_fields("block", lanes, max_pages,
                                     _windowed(spec)), packed)
    tables, temps, seeds, stop_ids = (f["tables"], f["temps"], f["seeds"],
                                      f["stops"])
    # (the window group's table rides every step where the model has one)
    groups = {"wtables": f["wtables"]} if "wtables" in f else {}
    window = spec.eva_window if spec is not None else 0
    lengths, tokens, active, steps_rem = (
        jnp.where(f["fresh"], f[name], kept) for name, kept in zip(
            ("lengths", "tokens", "active", "rem"), carry))

    def body(carry, _):
        kv, lens, toks, live, rem = carry
        nt, lp, _logits, kv, *moe = paged_decode_step(
            params, kv, tables, lens, toks, live,
            n_heads=n_heads, n_layers=n_layers,
            compute_dtype=compute_dtype, use_kernel=use_kernel,
            n_kv_heads=n_kv_heads, rope_theta=rope_theta,
            temps=temps, seeds=seeds, kernel_geometry=kernel_geometry,
            mesh=mesh, spec=spec, **groups)
        emitted = live
        nt = jnp.where(live, nt, toks)           # dead lanes hold position
        lens = lens + emitted.astype(jnp.int32)
        rem = rem - emitted.astype(jnp.int32)
        hit_stop = (nt[:, None] == stop_ids).any(axis=1)
        live = live & (rem > 0) & ~hit_stop
        if window:
            # a lane whose EVA window is full waits for its compaction,
            # which the host dispatches behind this block
            live = live & (lens % window != 0)
        return (kv, lens, nt, live, rem), (nt, lp, emitted, *moe)

    init = (kv_pool, lengths, tokens, active, steps_rem)
    (kv_pool, lengths, tokens, live, steps_rem), (toks, lps, ems, *moe) = \
        jax.lax.scan(body, init, None, length=k)
    # an expert model's counters, summed over the block's steps
    results = _pack_results(lanes, k, [m.sum(axis=0) for m in moe],
                            tokens=toks.T, logprobs=lps.T, emitted=ems.T)
    return results, lengths, tokens, live, steps_rem, kv_pool


def _device_sample_token(row, temp, seed2, pos):
    """Gumbel-max temperature sample of one lane: key folded from the full
    64-bit seed (lo, hi words) and the token position — the SINGLE
    definition of the device-sampling stream (the decode step vmaps it;
    the prefill first-token pick replays it on the fetched logits row so
    one request is one stream end to end)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(
        jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed2[0]), seed2[1]),
        pos)
    g = jax.random.gumbel(key, row.shape, jnp.float32)
    safe_t = jnp.where(temp > 0, temp, 1.0)
    sampled = jnp.argmax(row / safe_t + g)
    return jnp.where(temp > 0, sampled, jnp.argmax(row)).astype(jnp.int32)


def paged_ragged_forward(params, kv_pool, tables, seq, q_lens, kv_lens,
                         n_heads: int, n_layers: int, compute_dtype,
                         use_kernel: bool = False,
                         n_kv_heads: Optional[int] = None,
                         rope_theta: Optional[float] = None,
                         mesh=None,
                         kernel_geometry: Optional[tuple] = None,
                         last_only: bool = False, spec=None, wtables=None):
    """One fused multi-token forward over ragged per-lane segments in the
    PADDED form, every product on ``B x M`` rows (ROADMAP item 2, "Ragged
    Paged Attention" in PAPERS.md).  The K+1 speculative verify runs it
    (every lane's segment has one length there, so the padding is dense);
    a mixed round runs the same segments packed by token
    (:func:`paged_mixed_step`) and is tested against this form.

    ``seq (B, M)`` int32, left-packed: lane b's valid tokens are
    ``seq[b, :q_lens[b]]``, token j at global position
    ``kv_lens[b] - q_lens[b] + j``.  Per layer all valid positions' K/V
    scatter into the lane's pages first (invalid positions route to the
    reserved scratch page 0), then attention gathers the lane's whole
    block table masked by global causality: a row sees the cached
    context and the segment's own writes up to its position.  One
    static ``M`` serves every segment mix: plain decode (``q_lens=1``),
    K+1 speculative verify (``q_lens=k+1``), chunked prefill
    (``q_lens=chunk``) and any combination in one batch.

    ``use_kernel`` selects the pallas ragged kernel
    (:func:`tpulab.ops.ragged_attention.ragged_paged_attention`; under a
    ``mesh`` it shards on the KV-heads dim via shard_map) over the XLA
    dense-gather fallback.  ``last_only=True`` runs the vocab head over
    each lane's LAST valid position only and returns ``(logits (B,
    vocab), kv_pool)``; otherwise ``(logits (B, M, vocab), kv_pool)``
    with invalid positions' logits garbage the caller must not consume.
    The fused pool is donated by the caller either way.  A ``spec`` with
    expert layers appends their counters ``(n_moe, E + 2)`` (valid
    positions only) as a third element.
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    b, m = seq.shape
    page_size = _pages(kv_pool).shape[3]
    emb = params["embed"].astype(compute_dtype)
    x = emb[seq]                                      # (B, M, D)
    spec = _step_spec(spec, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    x = _streams(spec, x)
    valid = jnp.arange(m)[None, :] < q_lens[:, None]  # (B, M)
    pos = (kv_lens - q_lens)[:, None] + jnp.arange(m)[None, :]
    row = spec.cache_row(pos)      # the rows behind the positions
    # invalid positions' page index may run past the table width — XLA
    # clamps the gather, and the mask below discards the clamped id
    page_idx = jnp.where(valid,
                         jnp.take_along_axis(
                             tables,
                             jnp.clip(row // page_size, 0,
                                      tables.shape[1] - 1), axis=1), 0)
    slot_idx = jnp.where(valid, row % page_size, 0)
    # gather-after-scatter: token m sees cached context + the segment's
    # own writes up to its position (global causality); one program for
    # every segment mix
    seg = dict(tables=tables, q_lens=q_lens, kv_lens=_row_lens(spec, kv_lens),
               use_kernel=use_kernel, kernel_geometry=kernel_geometry,
               mesh=mesh)
    if spec.eva_window:
        seg["qpos"] = row
    if spec.window:
        # the window group's table and write targets (``wtables``, as
        # ``tables`` is the full group's)
        seg.update(wtables=wtables, wpage_idx=jnp.where(
            valid, jnp.take_along_axis(
                wtables, jnp.clip(row // page_size, 0,
                                  wtables.shape[1] - 1), axis=1), 0))
    moe_stats = []
    for layer in range(spec.n_layers):
        x, kv_pool, stats = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos, valid, kv_pool,
            page_idx, slot_idx, seg, compute_dtype)
        if stats is not None:
            moe_stats.append(stats)
    moe = (jnp.stack(moe_stats),) if moe_stats else ()

    x = _streams(spec, x, out=True)
    if last_only:
        # only each lane's last valid token seeds a pick: run the
        # vocab-sized head over ONE row per lane
        x = jnp.take_along_axis(
            x, jnp.maximum(q_lens - 1, 0)[:, None, None], axis=1)[:, 0]
    x = _rmsnorm(x, params["final_norm"]["scale"], spec.rms_eps)
    return (_lm_head(params, x), kv_pool) + moe


def round_width(prefill_tokens: int) -> int:
    """``M`` of the mixed round that carries ``prefill_tokens`` prompt
    tokens: the pow2 bucket its program is keyed by (few jits) and the
    segment width its attention is called at.  Never under 2: a one-token
    tail pads to two rows, which is one program less to trace, lower and
    load at every start (nine up to a budget of 512, as there were up to
    256: ``setup_s``)."""
    return max(2, 1 << (prefill_tokens - 1).bit_length())


def pack_round(lanes: int, prefill: Dict[int, Any], decode: Dict[int, int]):
    """Host half of :func:`paged_mixed_step`'s input: a round packed by
    token.  ``prefill`` maps a lane to its chunk's tokens (at least one
    token in all; packed in the mapping's order), ``decode`` a lane to its
    current token.  Returns numpy ``(toks (T,), row_lane (T,), row_off
    (T,), q_lens (lanes,))`` with ``T = round_width(prefill tokens) +
    lanes``."""
    m = round_width(sum(len(chunk) for chunk in prefill.values()))
    toks = np.zeros((m + lanes,), np.int32)
    row_lane = np.full((m + lanes,), -1, np.int32)
    row_off = np.zeros((m + lanes,), np.int32)
    q_lens = np.zeros((lanes,), np.int32)
    row = 0
    for lane, chunk in prefill.items():
        rows = slice(row, row + len(chunk))
        toks[rows], row_lane[rows] = chunk, lane
        row_off[rows] = np.arange(len(chunk))
        q_lens[lane] = len(chunk)
        row = rows.stop
    for lane, tok in decode.items():
        toks[m + lane], row_lane[m + lane], q_lens[lane] = tok, lane, 1
    return toks, row_lane, row_off, q_lens


def paged_mixed_step(params, kv_pool, packed, carry, lanes: int,
                     max_pages: int, n_heads: int, n_layers: int,
                     compute_dtype,
                     use_kernel: bool = False,
                     n_kv_heads: Optional[int] = None,
                     rope_theta: Optional[float] = None,
                     mesh=None,
                     kernel_geometry: Optional[tuple] = None, spec=None):
    """One mixed prefill+decode round, packed by token: a ragged forward
    over per-lane segments plus each lane's next-token pick, in ONE
    dispatch whose rows are the round's tokens.

    The round arrives as ONE buffer, ``packed`` (:func:`dispatch_fields`
    ``"round"``: ``tables``, ``q_lens``, ``kv_lens``, ``temps``, ``seeds``,
    ``fresh``, ``rem``, ``stops`` and ``rows``, the stack of ``toks``,
    ``row_lane``, ``row_off``), beside ``carry = (lengths, tokens, live,
    steps_rem)``: what the dispatch before this one returned, a decode
    block (:func:`paged_decode_block`) or a round.  The round is a member
    of the scheduler's chain: a decode row ``M + b`` whose lane is not
    ``fresh`` takes its token, its ``kv_len`` (the carried length + 1), its
    step budget and whether it runs at all (``live``: a lane that hit a
    stop token in the dispatch before holds no row here, writes nothing
    and emits nothing) from the carry, so the host plans and enqueues the
    round before it has fetched its predecessor.  A ``fresh`` lane takes
    them from the buffer; a chain's first round is fresh in every lane
    beside a carry nobody reads, so both are the same compiled program.
    Prefilling lanes carry a prompt chunk (``q_lens = chunk``), decoding
    lanes their current token (``q_lens = 1``), idle lanes nothing
    (``q_lens = 0``).  ``toks (T,)`` holds the round with ``T = M +
    lanes``: rows ``[0, M)`` are the prefilling lanes' chunk tokens one
    lane after the other, row ``M + b`` is lane b's decode token.
    ``row_lane (T,)`` is each row's lane (-1: the row holds no token) and
    ``row_off (T,)`` its offset in the lane's segment: token ``(b, j)``
    sits at global position ``kv_lens[b] - q_lens[b] + j``.  Embedding,
    norms, projections, RoPE, the row scatter into the lane's pages,
    ``wo``, the FFN or the routed experts and their counters run on the T
    rows; the attention is called once a chunk lane on that lane's ``M``
    rows and once for the decode rows at ``(B, 1)`` (:func:`_kv_walk`; the
    latent and sparse attention once a segment kind, the chunk rows in the
    ``(B, M)`` form of :func:`paged_ragged_forward`:
    :func:`_segment_calls`), so a round costs what its
    tokens cost, not lanes x the longest chunk.  ``M`` (from the shapes,
    ``T - lanes``) is the ONE number the program is keyed by.

    Every lane's pick is :func:`_device_sample_token` on its LAST valid
    row's logits at position ``kv_lens - 1`` — exactly the decode tick's
    stream for decode lanes and exactly the prefill first-token stream
    (position ``t - 1``) for lanes finishing their prompt, so one request
    is one (seed, position)-keyed stream regardless of which dispatch
    kind served it.  The caller consumes picks only for lanes that emit
    this round (a mid-prompt chunk's pick is discarded; device sampling
    is stateless, so a discarded pick costs nothing).

    Returns ``(results, last_logits (B, vocab), lengths (B,), last_tokens
    (B,), live (B,), steps_rem (B,), kv_pool)``: ``results`` is
    ONE int32 array, :func:`result_fields` ``(lanes, moe=...)``:
    ``tokens (B,)``, ``logprobs (B,)`` as their bits and the expert
    layers' counters where ``spec`` has any; ``last_logits`` stays
    device-resident unless a host-sampled lane fetches its row.  The same
    segments through ``paged_ragged_forward(last_only=True)`` give the
    same logits: that is the plain form this one is tested against.
    ``lengths`` .. ``steps_rem`` are the carry AFTER the round, device
    arrays a block or a round behind this one starts from: a lane that
    emitted here (a decode row that ran; a chunk whose prompt ENDS in this
    round, which the host marks with ``rem`` > 0) has its new length, its
    pick and the block body's liveness (budget left, no stop token, short
    of its EVA window's end); a lane in mid-prompt or idle is not live.

    For a ``spec`` with Mamba layers ``kv_pool`` is the pair ``(page store,
    lane state)``, in and out: each lane's segment runs the convolution and
    the scan from its own slot (from zeros where it starts at position 0)
    and leaves its last row's state there; a lane without a segment keeps
    what it held (:func:`_mamba_mixer`).
    """
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    f = unpack_words(dispatch_fields("round", lanes, max_pages,
                                     _windowed(spec)), packed)
    tables, q_lens, kv_lens = f["tables"], f["q_lens"], f["kv_lens"]
    toks, row_lane, row_off = f["rows"]
    b, t = lanes, toks.shape[0]
    m = t - b
    # the decode rows the host planned; those of a lane that is not fresh
    # are the carry's: its token, its length, and no row at all where the
    # dispatch before ended the lane
    c_len, c_tok, c_live, c_rem = carry
    planned = row_lane[m:] >= 0
    kept = planned & ~f["fresh"]
    runs = jnp.where(kept, c_live, planned)
    toks = toks.at[m:].set(jnp.where(kept, c_tok, toks[m:]))
    row_lane = row_lane.at[m:].set(jnp.where(runs, row_lane[m:], -1))
    q_lens = jnp.where(kept, runs.astype(jnp.int32), q_lens)
    kv_lens = jnp.where(kept, jnp.where(runs, c_len + 1, 0), kv_lens)
    steps_rem = jnp.where(kept, c_rem, f["rem"])
    page_size = _pages(kv_pool).shape[3]
    emb = params["embed"].astype(compute_dtype)
    x = emb[toks][None]                               # (1, T, D)
    spec = _step_spec(spec, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    x = _streams(spec, x)
    valid = row_lane >= 0
    lane = jnp.maximum(row_lane, 0)
    start = kv_lens - q_lens                          # (B,) segment starts
    pos = jnp.where(valid, start[lane] + row_off, 0)
    row = spec.cache_row(pos)      # the rows behind the positions
    page_idx = jnp.where(valid, tables[lane, row // page_size], 0)
    slot_idx = jnp.where(valid, row % page_size, 0)
    # the slot of the padded (B, M) form behind each row, and the row
    # behind each slot; slots past a lane's segment read row 0, which the
    # attention masks by q_lens
    back = lane * m + row_off
    spread = jnp.zeros((b * m,), jnp.int32).at[
        jnp.where(valid, back, b * m)].set(
            jnp.arange(t, dtype=jnp.int32), mode="drop")
    qpos = spec.cache_row(start)[:, None] + jnp.arange(m)[None, :]
    # the layout says which kind a lane's segment is: its decode token, if
    # it has one, is row M + b; every other segment is a chunk in [0, M)
    decodes = valid[m:]
    seg = dict(tables=tables, q_lens=q_lens, kv_lens=_row_lens(spec, kv_lens),
               use_kernel=use_kernel, kernel_geometry=kernel_geometry,
               mesh=mesh, row_seg=(row_lane, row_off),
               rows=(spread, back, qpos, jnp.where(decodes, 0, q_lens),
                     decodes.astype(jnp.int32)))
    if spec.window:
        seg.update(wtables=f["wtables"], wpage_idx=jnp.where(
            valid, f["wtables"][lane, row // page_size], 0)[None])
    moe_stats = []
    for layer in range(spec.n_layers):
        x, kv_pool, stats = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos[None], valid[None],
            kv_pool, page_idx[None], slot_idx[None], seg, compute_dtype)
        if stats is not None:
            moe_stats.append(stats)
    moe = (jnp.stack(moe_stats),) if moe_stats else ()

    # the vocab-sized head over ONE row a lane: its last valid token's
    last_row = spread[jnp.arange(b) * m + jnp.maximum(q_lens - 1, 0)]
    last = _lm_head(params, _rmsnorm(_streams(spec, x[0][last_row], out=True),
                                     params["final_norm"]["scale"],
                                     spec.rms_eps))
    pos_last = jnp.maximum(kv_lens - 1, 0)
    next_tokens = jax.vmap(_device_sample_token)(
        last, f["temps"], f["seeds"], pos_last)
    logp_rows = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
    logprobs = jnp.take_along_axis(logp_rows, next_tokens[:, None],
                                   axis=-1)[:, 0]
    # the carry after the round, by the block body's rule: a lane that
    # emitted (a decode row, a prompt's last chunk) goes on while it has
    # budget left, drew no stop token and stands short of its window's end
    emitted = (q_lens > 0) & (steps_rem > 0)
    steps_rem = steps_rem - emitted.astype(jnp.int32)
    hit_stop = (next_tokens[:, None] == f["stops"]).any(axis=1)
    live = emitted & (steps_rem > 0) & ~hit_stop
    if spec.eva_window:
        live = live & (kv_lens % spec.eva_window != 0)
    return (_pack_results(lanes, None, moe, tokens=next_tokens,
                          logprobs=logprobs), last, kv_lens, next_tokens,
            live, steps_rem, kv_pool)


def paged_eva_compact(params, kv_pool, pages, spec, use_kernel: bool = False):
    """Compact ONE finished EVA window of one lane, every layer, in place.

    ``pages (W / S,)`` int32: the pages that hold the window's ``W =
    spec.eva_window`` rows, in order (the page size ``S`` is
    ``spec.eva_chunk``, so a page is a chunk).  Every page becomes one
    summary row ``(k~, v~)`` (:func:`tpulab.ops.eva_summary.
    summarize_chunks` with the layers' ``eva_mu`` / ``eva_phi``), and the
    ``W / S`` summaries are written over the window's first ``W / S / S``
    pages: the rows the lane's table holds for the window from then on.
    The other pages keep what they held; the host returns them to the pool.
    Functional, so no summary lands on a chunk not yet read.  Returns the
    page store, donated by the caller."""
    import jax.numpy as jnp
    from tpulab.ops.eva_summary import summarize_chunks

    layers = range(spec.n_layers)
    mu = jnp.stack([params[f"layer{i}"]["eva_mu"] for i in layers])
    phi = jnp.stack([params[f"layer{i}"]["eva_phi"] for i in layers])
    rows = summarize_chunks(kv_pool, pages, mu, phi, use_kernel=use_kernel)
    size = kv_pool.shape[3]
    kept = pages.shape[0] // size
    # (L, pages, 2, row) -> (L, pages / S, 2, S, row): S summaries a page
    rows = rows.reshape(spec.n_layers, kept, size, 2, -1).transpose(
        0, 1, 3, 2, 4)
    return kv_pool.at[:, pages[:kept]].set(rows)


def paged_speculative_block(params, draft_params, kv_pool, packed,
                            lanes: int, max_pages: int,
                            n_heads: int, n_layers: int,
                            draft_n_heads: int, draft_n_layers: int,
                            compute_dtype, k: int = 4,
                            n_kv_heads: Optional[int] = None,
                            draft_n_kv_heads: Optional[int] = None,
                            rope_theta: Optional[float] = None,
                            use_kernel: bool = False, mesh=None,
                            kernel_geometry: Optional[tuple] = None):
    """Speculative decode: draft-propose + target-verify + per-lane
    accept/reject, ALL inside one device dispatch.

    A small draft model proposes ``k`` tokens per lane (a ``lax.scan``
    of single-token draft steps through a SECOND page table on the same
    fused pool), the target model verifies the current token plus all k
    proposals in ONE batched forward (:func:`_paged_verify_forward`),
    and acceptance runs on device: each lane emits the longest prefix of
    proposals matching the target's own choices, plus the target's
    correction (or bonus) token — so emitted tokens are EXACTLY the
    non-speculative stream, and one dispatch emits up to ``k + 1``
    tokens instead of ``k``.  The target's "choice" is
    :func:`_device_sample_token` at each position — greedy argmax for
    temp==0 lanes, and for device-sampled lanes the same
    (seed, position)-folded stream plain blocks use, so token parity is
    bit-exact in both modes.  The draft proposes through the SAME
    sampling function on its own logits (a perfect draft then reaches
    full acceptance under sampling too).

    Stop-mask machinery matches :func:`paged_decode_block`: a stop token
    is emitted as the lane's final token and truncates the emission; the
    per-lane steps-remaining budget caps it, and writes past the budget
    route to the scratch page (so a full-K block at the tail of a
    request can never write past the positions its reservation covers).
    Dead lanes emit nothing and write only scratch.  The draft scan runs
    ``k + 1`` iterations (last proposal discarded) so a fully-accepted
    round leaves no hole in the draft KV — the dense
    :class:`~tpulab.engine.speculative.SpeculativeGenerator` trick.
    Rejected proposals leave stale K/V past the accepted horizon in both
    tables; positions only advance, so every stale slot is overwritten
    before any later query may attend it.

    The CALLER pre-allocates BOTH tables to cover positions
    ``lengths .. lengths + k`` (see ``_reserve_spec_pages``).
    ``use_kernel`` routes attention on BOTH models through the ragged
    pallas kernel family (draft proposal steps at q=1, the verify
    forward at q=k+1 — the PR 7 follow-up retired); the XLA gather is
    the fallback, and under a ``mesh`` the kernel shards on KV heads.

    The host's side arrives as ONE buffer, ``packed``
    (:func:`dispatch_fields` ``"spec"``: a block's fields with the
    ``draft_tables`` in place of ``fresh``).  Returns ``(results, lengths
    (B,), last_tokens (B,), live (B,), steps_rem (B,), kv_pool)``,
    ``results`` ONE int32 array (:func:`result_fields` ``(lanes, k + 1,
    spec=True)``): ``tokens (B, k+1)``, ``logprobs (B, k+1)`` as their
    bits, the ``emitted (B, k+1)`` prefix mask, ``drafted (B,)`` and
    ``accepted (B,)``.
    """
    import jax
    import jax.numpy as jnp

    f = unpack_words(dispatch_fields("spec", lanes, max_pages), packed)
    tables, draft_tables, lengths, tokens, active = (
        f["tables"], f["draft_tables"], f["lengths"], f["tokens"],
        f["active"])
    temps, seeds, steps_rem, stop_ids = (f["temps"], f["seeds"], f["rem"],
                                         f["stops"])

    # 1) draft proposes k tokens per lane through the second page table;
    #    iterations past a lane's step budget write only scratch (their
    #    proposals can never be emitted)
    def dbody(carry, i):
        kv, tok = carry
        nt, _lp, _lg, kv = paged_decode_step(
            draft_params, kv, draft_tables, lengths + i, tok,
            active & (i < steps_rem),
            n_heads=draft_n_heads, n_layers=draft_n_layers,
            compute_dtype=compute_dtype, use_kernel=use_kernel,
            n_kv_heads=draft_n_kv_heads, rope_theta=rope_theta,
            temps=temps, seeds=seeds, kernel_geometry=kernel_geometry,
            mesh=mesh)
        return (kv, nt), nt

    (kv_pool, _), props = jax.lax.scan(dbody, (kv_pool, tokens),
                                       jnp.arange(k + 1))
    drafts = props[:k].T                               # (B, k)

    # 2) target verifies [cur, d_0..d_{k-1}] in ONE batched ragged
    #    forward (q_lens = the valid prefix per lane); position j's
    #    write is real only while the lane can still emit token j
    #    (emitted n <= steps_rem, and query j consumes writes 0..j only,
    #    so masking j >= steps_rem discards nothing live)
    seq = jnp.concatenate([tokens[:, None], drafts], axis=1)  # (B, k+1)
    q_lens = jnp.where(active,
                       jnp.minimum(k + 1, jnp.maximum(steps_rem, 0)), 0)
    logits, kv_pool = paged_ragged_forward(
        params, kv_pool, tables, seq, q_lens, lengths + q_lens,
        n_heads=n_heads, n_layers=n_layers, compute_dtype=compute_dtype,
        use_kernel=use_kernel, n_kv_heads=n_kv_heads,
        rope_theta=rope_theta, mesh=mesh, kernel_geometry=kernel_geometry)

    # 3) the target's own choice at every position — the same sampling
    #    stream as plain blocks, so the output is bit-identical
    pos = lengths[:, None] + jnp.arange(k + 1)[None, :]
    cand = jax.vmap(jax.vmap(_device_sample_token,
                             in_axes=(0, None, None, 0)))(
        logits, temps, seeds, pos)                      # (B, k+1)
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lps = jnp.take_along_axis(lsm, cand[..., None], axis=-1)[..., 0]

    # 4) accept/reject + stop-mask, on device: emit the agreeing prefix
    #    + correction, truncated by stop tokens and steps remaining
    agree = drafts == cand[:, :k]
    acc = jnp.cumprod(agree.astype(jnp.int32), axis=1).sum(axis=1)  # (B,)
    avail = acc + 1                     # accepted prefix + correction
    hit = (cand[:, :, None] == stop_ids[:, None, :]).any(axis=2)
    first_stop = jnp.argmax(hit, axis=1)
    stop_cap = jnp.where(hit.any(axis=1), first_stop + 1, k + 1)
    n = jnp.minimum(jnp.minimum(avail, stop_cap), steps_rem)
    n = jnp.where(active, n, 0)
    emitted = jnp.arange(k + 1)[None, :] < n[:, None]   # (B, k+1)
    lengths = lengths + n
    last = jnp.take_along_axis(cand, jnp.maximum(n - 1, 0)[:, None],
                               axis=1)[:, 0]
    tokens = jnp.where(n > 0, last, tokens).astype(jnp.int32)
    steps_rem = steps_rem - n
    stopped = hit.any(axis=1) & (stop_cap <= n)
    live = active & (steps_rem > 0) & ~stopped
    drafted = jnp.where(active, k, 0)
    accepted = jnp.where(active, jnp.minimum(acc, n), 0)
    results = _pack_results(
        lanes, k + 1, [], spec=True, tokens=cand.astype(jnp.int32),
        logprobs=lps, emitted=emitted, drafted=drafted, accepted=accepted)
    return results, lengths, tokens, live, steps_rem, kv_pool


def paged_extend(params, kv_pool, tables, tokens, start, valid_total,
                 n_heads: int, n_layers: int, compute_dtype,
                 n_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None):
    """A tail of tokens against EXISTING paged context, one lane: what the
    speculative draft's warm-up runs (``StepPrograms.draft_extend``, the
    scheduler's ``_warm_draft``) to fill the draft's page table with the
    context it is missing.  Prompts themselves ride mixed rounds
    (:func:`paged_mixed_step`).

    One fused forward over M tail tokens (positions ``start ..
    start+M-1``) for a single lane whose positions ``[0, start)`` are
    already resident in the pool.  Per layer the tail K/V scatter into
    their pages first, then attention gathers the lane's WHOLE block table:
    the gather-after-scatter sees resident context and tail together, so
    the mask is just global causality (tail token m attends position j iff
    ``j <= start+m``).

    tokens (1, M_pad) int32 (padded tail arbitrary); start scalar int32;
    valid_total scalar int32 = true total length (context so far + tail);
    tables (MP,) page ids covering all of it.  Returns (logits of the last
    valid token (vocab,), kv_pool) — the fused pool donated by the caller.
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    page_size = kv_pool.shape[3]
    m_pad = tokens.shape[1]
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens]                                   # (1, M_pad, D)
    spec = _step_spec(None, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    pos = start + jnp.arange(m_pad)                   # global positions
    valid = pos < valid_total
    page_idx = jnp.where(valid, tables[pos // page_size], 0)  # pad -> scratch
    slot_idx = jnp.where(valid, pos % page_size, 0)
    # gather-after-scatter: context = cached prefix + this tail
    seg = dict(tables=tables[None], use_kernel=False)
    for layer in range(n_layers):
        x, kv_pool, _ = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos[None], valid[None],
            kv_pool, page_idx, slot_idx, seg, compute_dtype)

    # only the last valid token's logits are ever consumed — run the
    # vocab-sized head over ONE row, not all M_pad rows
    x_last = x[0, valid_total - 1 - start][None]      # (1, D)
    x_last = _rmsnorm(x_last, params["final_norm"]["scale"])
    last = _lm_head(params, x_last)[0]                # (vocab,)
    return last, kv_pool


#: process-level memo of jitted engine programs (see StepPrograms._jit):
#: identical-geometry engines share one jitted callable and therefore one
#: compiled-program cache.  Bounded by the process's program-config
#: variety; entries hold compiled executables, never parameter or pool
#: buffers (those are traced arguments).
_JIT_MEMO: Dict[Any, Any] = {}
_JIT_MEMO_LOCK = threading.Lock()


class StepPrograms:
    """The jitted step programs of one engine plan
    (:class:`~tpulab.engine.plan.EnginePlan`), under the parameters'
    (``psh``), the page store's and the replicated sharding (None without a
    mesh).  ``tick``, ``mixed``, ``compact`` and ``draft_extend``
    (None where the plan has no such program) hold the jitted callable
    itself, as does what :meth:`block` and :meth:`spec_block` return: the
    scheduler calls it where it dispatches, with no Python frame between
    its call and the program's ``pallas_call`` s (a frame there costs
    ``setup_s``: :func:`_layer_block`)."""

    def __init__(self, plan, psh=None, kvsh=None, rep=None, hbm=None,
                 draft_psh=None):
        self.mesh, self.hbm = plan.mesh, hbm
        #: what each program takes from the host, as fields of one buffer
        self.fields = {kind: dispatch_fields(kind, plan.lanes, plan.max_pages,
                                             _windowed(plan.spec))
                       for kind in ("tick", "block", "spec", "round")}
        step_kw = plan.step_kw
        # a program: function, bound keywords, donated arguments, in and out
        # shardings.  Every array argument is positional (a sharded jit
        # attaches in_shardings by position), the host's one packed buffer
        step = ((1,), (psh, kvsh, rep), (rep, rep, kvsh))
        chained = ((1,), (psh, kvsh, rep, rep), (rep,) * 6 + (kvsh,))
        table = {
            # the K=1 tick
            "tick": (paged_decode_step_sampled, step_kw) + step,
            # mixed prefill+decode rounds: ONE program respecializes per
            # pow2 bucket of the round's prefill tokens (round_width): the
            # chunks packed by token and a row for each lane's decode token
            # through a single ragged forward + on-device pick
            "mixed": (paged_mixed_step, step_kw) + chained,
        }
        self.compact = self.draft_extend = None
        if plan.eva_window:
            # EVA: a finished window's rows compacted into its summaries,
            # one lane a dispatch; never fetched
            table["compact"] = (
                paged_eva_compact,
                dict(spec=plan.spec, use_kernel=plan.use_kernel),
                (1,), (psh, kvsh, rep), kvsh)
        if plan.draft:
            # draft-table warm-up: one fused draft forward over whatever
            # context tail the second table is missing (never synced)
            table["draft_extend"] = (
                paged_extend,
                dict(compute_dtype=plan.compute_dtype,
                     rope_theta=plan.rope_theta, **plan.draft),
                (1,), (draft_psh, kvsh, rep, rep, rep, rep), (rep, kvsh))
        for name, (fn, kw, donate, in_sh, out_sh) in table.items():
            setattr(self, name,
                    self._jit(partial(fn, **kw), donate, in_sh, out_sh))
        # the block programs, compiled once per block size in use
        self._block = (paged_decode_block, step_kw, (1,),
                       (psh, kvsh, rep, rep), (rep,) * 5 + (kvsh,))
        self._spec_block = (
            paged_speculative_block,
            dict({k: v for k, v in step_kw.items() if k != "spec"},
                 **{"draft_" + k: v for k, v in (plan.draft or {}).items()}),
            (2,), (psh, draft_psh, kvsh, rep), (rep,) * 5 + (kvsh,))
        self.blocks: Dict[int, Any] = {}
        self.block_names: Dict[int, str] = {}   # K -> the program's name
        self.spec_blocks: Dict[int, Any] = {}

    def block(self, k: int):
        """Jitted K-step fused decode (compiled once per block size)."""
        if k not in self.blocks:
            self.blocks[k] = self._sized(self._block, k)
            self.block_names[k] = f"paged_decode_block_k{k}"
        return self.blocks[k]

    def spec_block(self, k: int):
        """Jitted speculative block (compiled once per draft length)."""
        if k not in self.spec_blocks:
            self.spec_blocks[k] = self._sized(self._spec_block, k)
        return self.spec_blocks[k]

    @staticmethod
    def expert_products(spec):
        """The grouped products the step programs of ``spec``'s expert
        layers were traced at (a decode step's and each round width's rows
        x top-k, at the experts' two widths) with the kernel's tiles there,
        ``tiles`` None where ``ragged_dot`` is kept: host data written at
        trace time (``tpulab.ops.grouped_matmul.traced_products``), shared
        by the process's engines of one width like the memo's programs."""
        from tpulab.ops.grouped_matmul import traced_products
        f = spec.moe_ff_served      # relu2: ONE matrix in, padded to lanes
        widths = ((spec.d_model, f if spec.expert_act == "relu2" else 2 * f),
                  (f, spec.d_model))
        return [p for p in traced_products() if (p["k"], p["n"]) in widths]

    def _sized(self, program, k: int):
        fn, kw, *how = program
        return self._jit(partial(fn, k=k, **kw), *how)

    def _jit(self, fn, donate, in_sh, out_sh):
        """``jax.jit`` with explicit in/out shardings under a mesh — the
        partitioner then inserts the collectives (psum after row-parallel
        matmuls, gathers where layouts demand) INSIDE the compiled
        program — and a plain single-device jit otherwise (``in_sh`` /
        ``out_sh`` ignored; mesh=None is exactly the pre-mesh build).

        Jitted programs are shared through a process-level memo
        (:data:`_JIT_MEMO`) keyed by the function + its baked static
        config + donation + shardings: engines with identical program
        geometry (test suites, fleets of loopback replicas, bench
        modes) reuse one compiled-program cache instead of re-tracing
        and re-compiling identical HLO per engine.  Params and pools
        are traced ARGUMENTS, never baked, so sharing is purely a
        compile-time dedupe; configs with unhashable baked state fall
        back to a private jit.

        With an arbiter measuring scratch, the (shared) jit is wrapped
        per engine so each distinct shape signature records its
        compile-time temp bytes as a ``("scratch", ...)`` ledger claim
        (tpulab.hbm.scratch) — the third tenant the pre-arbiter
        headroom math never saw."""
        import jax

        base = getattr(fn, "func", fn)
        if fn is not base:
            # a bare partial is ``jit__unknown`` in a trace: name the
            # program after its function (+ the block size it binds)
            k = fn.keywords.get("k")
            fn.__name__ = base.__name__ + (f"_k{k}" if k is not None else "")

        def build():
            if self.mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=in_sh, out_shardings=out_sh)

        try:
            key = (base.__module__, base.__qualname__,
                   getattr(fn, "args", ()),
                   tuple(sorted(getattr(fn, "keywords", {}).items())),
                   donate,
                   in_sh if self.mesh is not None else None,
                   out_sh if self.mesh is not None else None)
            hash(key)
        except TypeError:
            key = None
        if key is None:
            jitted = build()
        else:
            with _JIT_MEMO_LOCK:
                jitted = _JIT_MEMO.get(key)
            if jitted is None:
                jitted = build()
                with _JIT_MEMO_LOCK:
                    jitted = _JIT_MEMO.setdefault(key, jitted)
        if self.hbm is not None and self.hbm.measure_scratch:
            from tpulab.hbm import MeasuredJit
            name = getattr(getattr(fn, "func", fn), "__name__", "jit")
            jitted = MeasuredJit(jitted, self.hbm, name)
        return jitted
