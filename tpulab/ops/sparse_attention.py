"""Learned sparse attention over paged K/V: the indexer's scores, the
selection, and attention over the selected keys (DeepSeek-Sparse-Attention
as ``tpulab.models.spec`` describes it for ``keye_vl2``).

Everything here works on *rows*: ``R`` query tokens, each with the lane it
belongs to (``row_lane``, -1 = the row holds no token) — a decode step's
``B`` rows, a packed round's chunk rows or its decode rows, the ``B * M``
rows of the padded form.  Three steps a layer:

:func:`index_scores`   ``I (R, W)`` float32, row ``r`` against every key
    slot ``s < W = max_pages * page_size`` of ITS lane: ``sum_i c_ri *
    relu(a_ri . b_s)`` over the index heads.  The lanes' index rows come
    gathered by page (``index_pool[layer][tables]``, whole pages: the one
    gather XLA does at speed) and the kernel ``dsa_index_scores`` reads them
    a key block at a time, a lane after the other, every head of a block in
    VMEM: no ``(rows, heads, keys)`` array exists in HBM.  Blocks past a
    lane's context and lanes without a row are neither fetched nor
    computed; what they leave in ``I`` is masked by the caller.
:func:`select_topk`    the mask ``(R, W)`` of the ``k`` largest live scores
    of each row — all of them where a row has at most ``k`` — exactly ``k``
    whatever ties there are (the lower key first, as ``lax.top_k`` breaks
    them).  The k-th largest score is found by bisection on the scores'
    bits, not by a sort: on a v5e ``lax.top_k`` of 264 rows x 32,768 keys
    took 6.25 ms where the bisection takes 0.30 (PR 34).
:func:`sparse_attend`  softmax attention of each row over the keys its mask
    row selects.  The kernel ``sparse_paged_attention`` walks a lane's pages
    once for all the rows of the call (the walk of
    :mod:`tpulab.ops.ragged_attention`) and reads the mask a page block at
    a time; a lane that holds no row of the call is skipped.  One row a
    lane (a decode step, a round's decode rows) goes through
    :func:`sparse_attend_decode`, the same walk with the query heads of a
    KV head stacked into the rows of one dot (the block's work is
    :func:`tpulab.ops.ragged_attention._stacked_block`, which the dense
    K/V walk at one row shares: this one's mask is the slice of the row's
    mask, that one's positional).  Both kernels feed the MXU
    by :func:`tpulab.ops.ragged_attention.mxu_operands`: a bf16 store gives
    both products bf16 operands in one pass (the probabilities rounded as
    :func:`sparse_attend_xla` rounds them), a float32 store ``HIGHEST``.

Why a masked walk for a decode row too, where 2,048 chosen rows would do:
on the v5e an XLA gather of 8 x 2,048 chosen (page, slot) rows of K and V
out of the fused page store took 7.8 ms a layer (0.47 us a row: a bf16 row
is half of a packed sublane of its page's tile, so each row is a slice of
its own) — PERF.md section 6.

``use_kernel=False`` (and every shape in interpret mode on the CPU) runs
the same mathematics in plain XLA: :func:`index_scores_xla`,
:func:`sparse_attend_xla`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpulab.ops.ragged_attention import (_NEG, _VMEM_REQUEST_MAX,
                                         _VMEM_SCOPED_DEFAULT, _page_walk,
                                         _plan, _stacked_block,
                                         _stacked_carry, _stacked_operands,
                                         _stacked_plan, _stacked_store,
                                         _staged_rows, _table_runs,
                                         _walk_scratch, _zero_rows_past,
                                         mxu_operands)

#: key slots one grid step of ``dsa_index_scores`` scores (a multiple of the
#: page size that divides the table's width; fewer steps of ~0.35 us each)
_SCORE_BLOCK_KEYS = 2048
#: rows at or under which :func:`select_topk` searches four bits an
#: iteration: a decode step's few rows are bound by the loop's 32 trips,
#: a chunk's 256 by the compares of one
_RADIX_ROWS = 64
_MASK_DTYPE = jnp.int8


# ---------------------------------------------------------------------------
# the indexer's scores
# ---------------------------------------------------------------------------

def index_scores_xla(a, c, row_lane, ictx):
    """The plain form: ``a (R, Hi, Di)``, ``c (R, Hi)`` float32, ``ictx (B,
    W, row >= Di)`` the lanes' gathered index rows.  Returns ``(R, W)``
    float32.  A lane at a time, every row against its keys (the rows of
    other lanes discarded): it materialises ``(R, Hi, W)``."""
    out = jnp.zeros((a.shape[0], ictx.shape[1]), jnp.float32)
    for lane in range(ictx.shape[0]):
        s = jnp.einsum("rhd,kd->rhk", a, ictx[lane, :, :a.shape[-1]],
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        out = jnp.where((row_lane == lane)[:, None],
                        (jax.nn.relu(s) * c[:, :, None]).sum(axis=1), out)
    return out


def _scores_kernel(src_lane_ref, src_blk_ref, live_ref, a_ref, c_ref,
                   lane_ref, ictx_ref, o_ref, *, n_heads: int, lanes: int,
                   precision):
    kb, lane = pl.program_id(0), pl.program_id(1)

    @pl.when(lane == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # a (lane, key block) pair that holds no live key of a row of the call
    # computes nothing, and its index map repeats the last live pair's
    # block, so nothing is fetched for it either
    @pl.when(live_ref[kb * lanes + lane] > 0)
    def _score():
        blk = ictx_ref[0]                                   # (G, row)

        def head(i, acc):
            s = jax.lax.dot_general(
                a_ref[i], blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            return acc + c_ref[i] * jnp.maximum(s, 0.0)

        acc = jax.lax.fori_loop(0, n_heads, head,
                                jnp.zeros(o_ref.shape, jnp.float32))
        o_ref[...] = jnp.where(lane_ref[...] == lane, acc, o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores(a, c, row_lane, ictx, lane_live, kv_lens, interpret: bool):
    r, hi, di = a.shape
    b, w, row = ictx.shape
    g = w
    while g > _SCORE_BLOCK_KEYS and g % 2 == 0:
        g //= 2
    nkb = w // g
    rp = -(-r // 16) * 16                       # whole packed bf16 tiles
    # heads lead, so that the kernel's loop over them indexes a leading
    # axis; queries zero-padded to the stored (zero-padded) row
    a = jnp.pad(a.transpose(1, 0, 2), ((0, 0), (0, rp - r), (0, row - di)))
    c = jnp.pad(c.astype(jnp.float32).T, ((0, 0), (0, rp - r)))[..., None]
    lane_col = jnp.pad(row_lane.astype(jnp.int32), (0, rp - r),
                       constant_values=-1)[:, None]
    # grid step (kb, lane) is live iff the lane has a row here and a key in
    # the block; a dead step maps to the last live step's block (no fetch)
    live = ((lane_live > 0)[None, :]
            & (jnp.arange(nkb)[:, None] * g < kv_lens[None, :])).reshape(-1)
    step = jnp.arange(nkb * b, dtype=jnp.int32)
    src = jax.lax.cummax(jnp.where(live, step, -1))
    src = jnp.where(src < 0, jnp.argmax(live).astype(jnp.int32), src)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # source lane, source block, live
        grid=(nkb, b),
        in_specs=[
            pl.BlockSpec((hi, rp, row), lambda kb, lane, *_: (0, 0, 0)),
            pl.BlockSpec((hi, rp, 1), lambda kb, lane, *_: (0, 0, 0)),
            pl.BlockSpec((rp, 1), lambda kb, lane, *_: (0, 0)),
            pl.BlockSpec((1, g, row), lambda kb, lane, sl, sb, lv: (
                sl[kb * b + lane], sb[kb * b + lane], 0)),
        ],
        out_specs=pl.BlockSpec((rp, g), lambda kb, lane, *_: (0, kb)),
    )
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.dtype(ictx.dtype).itemsize >= 4
                 else jax.lax.Precision.DEFAULT)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, n_heads=hi, lanes=b,
                          precision=precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(max(
            _VMEM_SCOPED_DEFAULT,
            6 * rp * g * 4 + 4 * g * row * 4 + 4 * hi * rp * (row + 128) * 4),
            _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="dsa_index_scores",
    )(src % b, src // b, live.astype(jnp.int32), a.astype(ictx.dtype), c,
      lane_col, ictx)
    return out[:r]


def index_scores(a, c, row_lane, ictx, lane_live, kv_lens,
                 use_kernel: bool = True, interpret: bool | None = None):
    """``I (R, W)`` float32: row ``r``'s index queries ``a (R, Hi, Di)``,
    weighted ``c (R, Hi)`` (the published scale folded in), against the
    gathered index rows ``ictx (B, W, row)`` of lane ``row_lane[r]``.
    ``lane_live (B,)``: the lanes that hold a row of the call; ``kv_lens
    (B,)``: their contexts.  Entries past a lane's context, of a lane that
    is not live and of a row without a token are NOT meaningful (zeros or
    another lane's): the caller masks them (:func:`select_topk`'s
    ``live``)."""
    if not use_kernel:
        return index_scores_xla(a, c, row_lane, ictx)
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _index_scores(a, c, row_lane, ictx, lane_live.astype(jnp.int32),
                         kv_lens.astype(jnp.int32), interpret)


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------

def select_topk(scores, live, k: int):
    """The mask ``(R, W)`` bool of each row's ``min(k, live keys)`` largest
    ``scores (R, W)`` float32 among the keys ``live (R, W)`` marks; ties at
    the k-th score go to the lower key (``lax.top_k``'s order), so a row
    selects exactly that many.

    The k-th largest is found on the scores' bits: a float's bits, with
    the low 31 flipped under a sign, order as the floats do, and the
    largest ``t`` with ``count(bits >= t) >= k`` is read off bit by bit
    (32 counts over the row; four bits a trip for few rows)."""
    r, w = scores.shape
    k = min(int(k), w)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(live, scores, -jnp.inf), jnp.int32)
    # order-preserving image in uint32: negative floats reversed, then the
    # sign bit flipped so that unsigned order is the floats' order
    key = jax.lax.bitcast_convert_type(
        bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF)), jnp.uint32
    ) ^ jnp.uint32(0x80000000)
    nbits = 4 if r <= _RADIX_ROWS else 1
    digits = jnp.arange(1, 1 << nbits, dtype=jnp.uint32)

    def trip(i, t):
        shift = (32 - nbits * (i + 1)).astype(jnp.uint32)
        cand = t[:, None] | (digits[None, :] << shift)           # (R, n)
        enough = (key[:, None, :] >= cand[:, :, None]).sum(-1) >= k
        # counts fall as the digit grows: the digits that still reach k
        return t | (enough.sum(-1).astype(jnp.uint32) << shift)

    thr = jax.lax.fori_loop(0, 32 // nbits, trip,
                            jnp.zeros((r,), jnp.uint32))[:, None]
    above = live & (key > thr)
    tied = live & (key == thr)
    need = k - above.sum(-1, keepdims=True)

    def by_rank(_):
        # more keys tie at the k-th score than there is room for
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= need))

    return jax.lax.cond((tied.sum(-1, keepdims=True) > need).any(),
                        by_rank, lambda _: above | tied, None)


# ---------------------------------------------------------------------------
# attention over the selected keys
# ---------------------------------------------------------------------------

def sparse_attend_xla(q, mask, row_lane, kv_layer, tables, compute_dtype):
    """The plain form: ``q (R, H, D)``, ``mask (R, W)`` bool, ``kv_layer
    (P, 2, S, Hkv*D)`` one layer of the page store.  A lane at a time: the
    lane's pages gathered densely, every row against them, the rows of
    other lanes discarded.  Returns ``(R, H, D)``; a row whose mask is
    empty gets zeros."""
    r, h, d = q.shape
    out = jnp.zeros((r, h, d), compute_dtype)
    for lane in range(tables.shape[0]):
        ctx = kv_layer[tables[lane]]                  # (MP, 2, S, Hkv*D)
        hkv = ctx.shape[-1] // d
        k_ctx = ctx[:, 0].reshape(-1, hkv, d)
        v_ctx = ctx[:, 1].reshape(-1, hkv, d)
        mine = mask & (row_lane == lane)[:, None]     # (R, W)
        qg = q.reshape(r, hkv, h // hkv, d).astype(jnp.float32)
        scores = jnp.einsum("rgjd,kgd->rgjk", qg, k_ctx.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST) / np.sqrt(d)
        scores = jnp.where(mine[:, None, None, :], scores, _NEG)
        probs = jax.nn.softmax(scores, axis=-1) * mine[:, None, None, :]
        got = jnp.einsum("rgjk,kgd->rgjd", probs.astype(compute_dtype),
                         v_ctx.astype(compute_dtype)).reshape(r, h, d)
        out = jnp.where((row_lane == lane)[:, None, None], got, out)
    return out


def _sparse_attn_kernel(layer_ref, tables_ref, runs_ref, live_ref, kvlens_ref,
                        q_ref, lane_ref, mask_ref, kvpool_ref, o_ref, kv_buf,
                        sem, mask_buf, msem, *,
                        page_size: int,
                        max_pages: int, n_heads: int, head_dim: int,
                        n_kv_heads: int, rows: int, sm_scale: float,
                        g_pages: int, nbuf: int):
    """One lane's pages against ALL the rows of the call: the rows of other
    lanes are masked and keep what ``o_ref`` holds (one block for the
    whole grid, zeroed by the first step).  Both products of a key block
    take their operands as ``mxu_operands`` reads them from the store's
    dtype (bf16 in one pass for a bf16 store, ``HIGHEST`` for float32)."""
    lane = pl.program_id(0)
    layer = layer_ref[0]

    @pl.when(lane == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live_ref[lane] > 0)
    def _lane():
        length = jnp.maximum(kvlens_ref[lane], 1) - 1
        h, d, hkv = n_heads, head_dim, n_kv_heads
        g = h // hkv
        gs = g_pages * page_size

        def mask_columns(j, slot, go):
            # beside a block's pages, the mask's columns for them
            go(pltpu.make_async_copy(
                mask_ref.at[:, pl.ds(pl.multiple_of(j * gs, gs), gs)],
                mask_buf.at[slot], msem.at[slot]))

        start_block, wait_block, live_blocks = _page_walk(
            tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer, length,
            page_size=page_size, max_pages=max_pages, g_pages=g_pages,
            nbuf=nbuf, also=mask_columns)

        dt, precision = mxu_operands(q_ref.dtype, kv_buf.dtype)
        q = (q_ref[...].astype(jnp.float32) * sm_scale).astype(dt)  # (R, H*D)
        dot_qk = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dot_pv = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        mine = lane_ref[...] == lane                            # (R, 1)

        def body(j, carry):
            slot = jax.lax.rem(j, nbuf)
            wait_block(j, slot)
            # (a block past the lane's pages has no trip)
            start_block(j + nbuf - 1, jax.lax.rem(j + nbuf - 1, nbuf))
            _zero_rows_past(kv_buf, slot, 1, j * gs, length)
            kblk = _staged_rows(kv_buf, slot, 0).astype(dt)  # (G*S, Hkv*D)
            vblk = _staged_rows(kv_buf, slot, 1).astype(dt)
            mask = jnp.logical_and(
                mask_buf[slot].astype(jnp.int32) != 0, mine)      # (R, G*S)
            maskf = mask.astype(jnp.float32)
            out = []
            for hh in range(h):
                m_c, l_c, acc_c = carry[hh]
                hk = hh // g
                s = dot_qk(q[:, hh * d:(hh + 1) * d],
                           kblk[:, hk * d:(hk + 1) * d])
                s = jnp.where(mask, s, _NEG)
                m_new = jnp.maximum(m_c, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_c - m_new)
                p = jnp.exp(s - m_new) * maskf
                out.append((m_new,
                            l_c * alpha + p.sum(axis=1, keepdims=True),
                            acc_c * alpha + dot_pv(
                                p.astype(dt),
                                vblk[:, hk * d:(hk + 1) * d])))
            return tuple(out)

        init = tuple((jnp.full((rows, 1), _NEG, jnp.float32),
                      jnp.zeros((rows, 1), jnp.float32),
                      jnp.zeros((rows, d), jnp.float32)) for _ in range(h))
        # the lane's live blocks: one past its length is never walked
        final = jax.lax.fori_loop(0, live_blocks, body, init)
        for hh in range(h):
            _m, l_c, acc_c = final[hh]
            cols = slice(hh * d, (hh + 1) * d)
            o_ref[:, cols] = jnp.where(
                mine, (acc_c / jnp.maximum(l_c, 1e-30)).astype(o_ref.dtype),
                o_ref[:, cols])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_attn(q, mask, row_lane, kv_pool, layer, tables, lane_live,
                 kv_lens, interpret: bool):
    r, h, d = q.shape
    page_size, row = kv_pool.shape[3], kv_pool.shape[4]
    hkv = row // d
    b, max_pages = tables.shape
    rp = -(-r // 32) * 32                        # whole packed int8 tiles
    g_pages, nbuf, need = _plan(rp, h, hkv, d, page_size, max_pages, q.dtype,
                                kv_pool.dtype)
    gs = g_pages * page_size
    # the mask's width in whole blocks, so that a block's columns exist
    wp = -(-max_pages // g_pages) * gs
    mask = jnp.pad(mask.astype(_MASK_DTYPE),
                   ((0, rp - r), (0, wp - mask.shape[1])))
    q2 = jnp.pad(q.reshape(r, h * d), ((0, rp - r), (0, 0)))
    lane_col = jnp.pad(row_lane.astype(jnp.int32), (0, rp - r),
                       constant_values=-1)[:, None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # layer, tables (flat), runs, live, kv_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((rp, h * d), lambda lane, *_: (0, 0)),
            pl.BlockSpec((rp, 1), lambda lane, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the mask stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # and so does the page store
        ],
        out_specs=pl.BlockSpec((rp, h * d), lambda lane, *_: (0, 0)),
        scratch_shapes=_walk_scratch(nbuf, g_pages, kv_pool) + [
            pltpu.VMEM((nbuf, rp, gs), _MASK_DTYPE),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
    )
    kernel = functools.partial(
        _sparse_attn_kernel, page_size=page_size, max_pages=max_pages,
        n_heads=h, head_dim=d, n_kv_heads=hkv, rows=rp,
        sm_scale=1.0 / np.sqrt(d), g_pages=g_pages, nbuf=nbuf)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, h * d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            max(_VMEM_SCOPED_DEFAULT,
                (need + 2 * nbuf * rp * gs) * 3 // 2), _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="sparse_paged_attention",
    )(layer, tables.reshape(-1), _table_runs(tables, g_pages), lane_live,
      kv_lens, q2, lane_col, mask, kv_pool)
    return out[:r].reshape(r, h, d)


def _sparse_decode_kernel(layer_ref, tables_ref, runs_ref, live_ref,
                          kvlens_ref, q_ref, mask_ref, kvpool_ref, o_ref,
                          kv_buf, sem, *,
                          page_size: int, max_pages: int, n_heads: int,
                          head_dim: int, n_kv_heads: int, sm_scale: float,
                          g_pages: int, nbuf: int):
    """One lane's ONE query row against the lane's pages: the query heads
    of a KV head are the rows of one dot (``H / Hkv`` rows, ``Hkv`` dots a
    block), where the rows kernel would make ``H`` dots of a padded tile of
    rows for the one that counts (PR 34: 24 of a decode step's 33 ms).
    The operands of both products follow ``mxu_operands``, as in the rows
    kernel: the store's dtype decides, nothing else."""
    lane = pl.program_id(0)
    layer = layer_ref[0]

    @pl.when(live_ref[lane] > 0)
    def _lane():
        length = jnp.maximum(kvlens_ref[lane], 1) - 1
        d, hkv = head_dim, n_kv_heads
        g = n_heads // hkv
        gs = g_pages * page_size
        start_block, wait_block, live_blocks = _page_walk(
            tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer, length,
            page_size=page_size, max_pages=max_pages, g_pages=g_pages,
            nbuf=nbuf)

        q, dot_qk, dot_pv = _stacked_operands(q_ref, kv_buf, sm_scale)

        def body(j, carry):
            slot = jax.lax.rem(j, nbuf)
            wait_block(j, slot)
            # (a block past the lane's pages has no trip)
            start_block(j + nbuf - 1, jax.lax.rem(j + nbuf - 1, nbuf))
            _zero_rows_past(kv_buf, slot, 1, j * gs, length)
            return _stacked_block(
                q, _staged_rows(kv_buf, slot, 0).astype(q.dtype),
                _staged_rows(kv_buf, slot, 1).astype(q.dtype),  # (G*S, Hkv*D)
                mask_ref[0, :, pl.ds(pl.multiple_of(j * gs, gs),
                                     gs)] != 0,                    # (1, G*S)
                carry, dot_qk, dot_pv)

        init = _stacked_carry(hkv, g, d)
        # the lane's live blocks: one past its length is never walked
        _stacked_store(o_ref, jax.lax.fori_loop(0, live_blocks, body, init))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_decode(q, mask, kv_pool, layer, tables, lane_live, kv_lens,
                   interpret: bool):
    b, h, d = q.shape
    page_size, row = kv_pool.shape[3], kv_pool.shape[4]
    hkv = row // d
    max_pages = tables.shape[1]
    g_pages, nbuf, need = _stacked_plan(h, hkv, d, page_size, max_pages,
                                        q.dtype, kv_pool.dtype)
    gs = g_pages * page_size
    wp = -(-max_pages // g_pages) * gs
    # a lane's mask row as a block of its own, (1, W): int32, so that a row
    # is whole sublanes
    mask = jnp.pad(mask.astype(jnp.int32),
                   ((0, 0), (0, wp - mask.shape[1])))[:, None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # layer, tables (flat), runs, live, kv_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda lane, *_: (lane, 0, 0)),
            pl.BlockSpec((1, 1, wp), lambda lane, *_: (lane, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the page store stays in HBM
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda lane, *_: (lane, 0, 0)),
        scratch_shapes=_walk_scratch(nbuf, g_pages, kv_pool),
    )
    kernel = functools.partial(
        _sparse_decode_kernel, page_size=page_size, max_pages=max_pages,
        n_heads=h, head_dim=d, n_kv_heads=hkv, sm_scale=1.0 / np.sqrt(d),
        g_pages=g_pages, nbuf=nbuf)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            max(_VMEM_SCOPED_DEFAULT, (need + 64 * wp) * 3 // 2),
            _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="sparse_paged_decode",
    )(layer, tables.reshape(-1), _table_runs(tables, g_pages), lane_live,
      kv_lens, q, mask, kv_pool)


def sparse_attend_decode(q, mask, kv_pool, layer, tables, lane_live, kv_lens,
                         interpret: bool | None = None):
    """:func:`sparse_attend` for ONE row a lane, row ``b`` lane ``b``'s (a
    decode step, a packed round's decode rows): ``q (B, H, D)``, ``mask (B,
    W)``.  The kernel ``sparse_paged_decode`` stacks the query heads of a KV
    head into the rows of one dot.  A lane that is not ``lane_live`` is
    skipped and its output row is UNWRITTEN: the caller reads none of it."""
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _sparse_decode(q, mask, kv_pool,
                          jnp.asarray(layer, jnp.int32).reshape(1),
                          tables.astype(jnp.int32),
                          lane_live.astype(jnp.int32),
                          kv_lens.astype(jnp.int32), interpret)


def sparse_attend(q, mask, row_lane, kv_pool, layer, tables, lane_live,
                  kv_lens, compute_dtype, use_kernel: bool = True,
                  interpret: bool | None = None):
    """Attention of rows ``q (R, H, D)`` over the keys ``mask (R, W)``
    selects among their lane's pages (``kv_pool (L, P, 2, S, Hkv*D)``, the
    whole page store; ``layer``, ``tables (B, MP)``, ``kv_lens (B,)`` as in
    :func:`~tpulab.ops.ragged_attention.ragged_paged_attention`).
    ``lane_live (B,)``: the lanes that hold a row of the call; the others
    are skipped.  Returns ``(R, H, D)``; a row that selects nothing (no
    token, or a lane that is not live) gets zeros."""
    if not use_kernel:
        return sparse_attend_xla(q, mask, row_lane, kv_pool[layer], tables,
                                 compute_dtype)
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _sparse_attn(q, mask, row_lane, kv_pool,
                        jnp.asarray(layer, jnp.int32).reshape(1),
                        tables.astype(jnp.int32),
                        lane_live.astype(jnp.int32),
                        kv_lens.astype(jnp.int32), interpret)
