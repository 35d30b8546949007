"""Pallas ragged paged-attention kernel family (decode / verify / prefill).

One entry, :func:`_ragged_attn`, serves every paged-attention shape the
engine dispatches ("Ragged Paged Attention", PAPERS.md): each lane carries
a *segment* of ``q_lens[b]`` query tokens ending at context position
``kv_lens[b] - 1`` over its own block table of KV pages.  Per-lane segment
lengths key the whole family:

- plain decode: ``q_lens = 1`` per live lane (the old single-query
  kernel's shape);
- K+1 speculative verify: ``q_lens = k + 1`` (current token + K draft
  proposals, verified in one pass);
- mixed chunked-prefill + decode rounds: prefilling lanes carry their
  chunk (``q_lens = chunk``), decoding lanes carry 1 — ONE fused program
  over the ragged batch instead of separate prefill and decode kinds.

Which shape takes which kernel — the rows' width ``M`` decides, at trace
time, and nothing else does (no argument, option or model name):

- ``M == 1`` (a decode step of ``paged_decode_block``; a packed round's
  decode rows) -> ``ragged_paged_decode`` (:func:`_ragged_decode_kernel`):
  the ``g = H / Hkv`` query heads of a KV head are the ROWS of one dot, so
  a key block costs ``Hkv`` dot pairs and its K and V pass the MXU once.
  Its per-block work is :func:`_stacked_block`, which
  ``sparse_paged_decode`` (:mod:`tpulab.ops.sparse_attention`) calls too:
  the two differ in the block's mask alone (positional here, a slice of
  the selection's mask row there).
- ``M > 1`` (a round's chunk rows, the K+1 verify form, the padded form)
  -> ``ragged_paged_attention`` (:func:`_ragged_attn_kernel`): a query
  head at a time, ``M`` rows a dot, ``H`` dot pairs a block.  At one row
  that was ``g`` passes of the same K and V block for one live row of a
  padded tile each (PR 40: 1.46 -> 0.42 ms a call at 32 lanes, 16 heads on
  2 KV heads of 256, ~3 k keys a lane, on a v5e).

A lane pays for the query rows it holds.  A grid step computes all ``M``
rows of its block whatever ``q_lens`` says, so ``M`` is the width the
caller chooses, and a lane with ``q_lens == 0`` is *skipped*: no page
DMA, no walk, no dot, its output block unwritten.  A packed round
(:func:`tpulab.engine.paged_steps.paged_mixed_step`) therefore calls
:func:`_ragged_attn` twice a layer, once a segment kind, on the same pages
and ``kv_lens``: the chunk rows at ``(B, M)`` with ``q_lens`` zeroed for
the lanes that hold no chunk, and the decode rows at ``(B, 1)``, a decode
step's shape, with ``q_lens`` zeroed for the lanes that hold a chunk or
nothing.  The padded form (K+1 verify) and the decode step call it once.

The XLA fallback gathers every lane's pages into a dense
``(B, MP*S, H, D)`` tensor; these kernels walk the block table per lane,
DMA-ing fused K/V pages from HBM into VMEM scratch through an
``nbuf``-deep slot-rotation prefetch pipeline over blocks of ``g_pages``
pages (:func:`_block_geometry`, :func:`_page_walk`), and accumulate
softmax online per query row — O(block) VMEM, no gather materialization,
dead pages skipped by predication.  A block whose table entries are an
ascending run of page ids is ``g_pages`` ADJACENT pages of the layer and
is ONE DMA; any other block is one DMA a page.  The table decides, block
by block (:func:`_table_runs`), and :class:`~tpulab.engine.kv_pool.
PagedKVPool` hands a lane its pages in runs: where a page is narrow the
walk is bound by the count of its DMAs, not by their bytes.

The MXU is fed what the store holds (:func:`mxu_operands`): a bf16 (or
fp8) store gives both products of a key block bf16 operands in one default
pass, the probabilities rounded to bf16 before the value product as the
XLA form of the step rounds them; a float32 store keeps both at
``HIGHEST``.  Running maximum, normaliser and accumulator are float32.

Per-head compute rides the flash-attention dot shapes (2D matmuls only,
the Mosaic-serialization-safe subset): in the rows kernel, for each query head, the block's scores are
``q_h (M, D) x k_h^T -> (M, G*S)`` and the weighted values
``p (M, G*S) x v_h -> (M, D)``; in the one-row kernel, for each KV head,
``q_g (g, D) x k^T -> (g, G*S)`` and ``p (g, G*S) x v -> (g, D)``; the
running (max, normalizer, accumulator) carried per head (per KV head)
through the block walk.  GQA stages pages in the compact ``Hkv`` form (the
bandwidth win) and slices each head's KV block statically in VMEM.

The pool goes in whole, ``(L, P, 2, S, Hkv*D)`` as
:class:`~tpulab.engine.kv_pool.PagedKVPool` keeps it, with the layer as one
more scalar-prefetch word: the page DMAs read ``kv_pool[layer, page]``,
and nothing slices or reshapes the pool ahead of the call (XLA cannot
fuse into a ``pallas_call`` operand: either was a copy of a whole layer
of the pool per call).

Sharded serving: ``mesh=`` wraps the kernel in ``shard_map`` over the
KV heads — each model-axis shard walks the SAME replicated block
tables but DMAs only its own heads' share of each page row (matching
``kv_pool_sharding``) and attends its own query heads (a shard holds whole
groups: ``g`` is what it is unsharded), so the kernel
composes with the tensor-parallel engine instead of being rejected at
construction.  ``interpret=True`` (automatic off TPU) runs the same
kernel on CPU for hermetic tests — tier-1 exercises the real kernel
path, sharded and not.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

_NBUF = 8  # max block-DMA groups in flight; clamped per geometry so K+V
# scratch stays within a VMEM budget (see _block_geometry)
_VMEM_BUDGET_BYTES = 8 << 20  # K+V staging combined; v5e VMEM is ~2x this
_TARGET_BLOCK_ROWS = 256  # aim each compute step at ~this many KV rows

_LANES, _SUBLANES = 128, 8         # one f32 vector register / tile
#: Mosaic's default scoped-VMEM limit; a kernel that needs more asks for it
_VMEM_SCOPED_DEFAULT = 16 << 20
#: the most this kernel asks for (a v5e core has 128 MiB of VMEM, and the
#: XLA fusions around the call keep their own share)
_VMEM_REQUEST_MAX = 96 << 20


def mxu_operands(q_dtype, kv_dtype):
    """``(dtype, precision)`` of BOTH matrix products of a key block, read
    from the page store's dtype and nothing else.  A store narrower than 32
    bits (bf16, fp8 pages) feeds the MXU the query's dtype in one default
    pass: the scaled query and the probabilities are rounded to it, the K
    and V blocks go as stored, the accumulation stays float32 — what the
    XLA form of the same step computes (``softmax(...).astype(compute)``
    before the value product).  A float32 store keeps float32 operands at
    ``HIGHEST`` (the default pass would round them to bf16)."""
    if jnp.dtype(kv_dtype).itemsize >= 4:
        return jnp.float32, jax.lax.Precision.HIGHEST
    return jnp.dtype(q_dtype), jax.lax.Precision.DEFAULT


def _block_geometry(page_size: int, max_pages: int, hd: int,
                    itemsize: int) -> tuple[int, int]:
    """(g_pages, nbuf): pages per compute block and pipeline depth.
    Total scratch (nbuf slots, double-buffer floor nbuf>=2) stays within
    the VMEM budget: g shrinks first, so wide geometries trade block size
    for a working pipeline rather than blowing VMEM."""
    page_bytes = 2 * page_size * hd * itemsize
    g = max(1, min(_TARGET_BLOCK_ROWS // page_size, max_pages,
                   _VMEM_BUDGET_BYTES // max(2 * page_bytes, 1)))
    nbuf = max(2, min(_NBUF, _VMEM_BUDGET_BYTES // max(g * page_bytes, 1)))
    return g, nbuf


def walk_block_pages(page_size: int, max_pages: int, row: int,
                     kv_dtype) -> int:
    """Pages a key block holds in every walk of the family over pages whose
    rows are ``row`` wide (:func:`_block_geometry`): the unit a table's
    runs are counted in (``debug_state()["pool"]``)."""
    return _block_geometry(page_size, max_pages, row,
                           jnp.dtype(kv_dtype).itemsize)[0]


def _plan(m: int, h: int, hkv: int, d: int, page_size: int, max_pages: int,
          q_dtype, kv_dtype, g_pages: int | None = None,
          nbuf: int | None = None) -> tuple[int, int, int]:
    """``(g_pages, nbuf, vmem_bytes)``: the block geometry (auto unless
    pinned) and the VMEM one grid step then holds, from the kernel's own
    shapes: the page pipeline, the double-buffered q/o blocks, the
    per-head (max, normalizer, accumulator) carry — live twice across a
    loop step, its (M, 1) columns padded to a full 128-lane tile — and
    the score tiles.  The K/V blocks go to the dots as staged
    (:func:`mxu_operands`), so nothing holds a copy of them.  Mosaic's
    own temporaries come on top: the caller leaves headroom."""
    def pad(n, to):
        return -(-n // to) * to
    q_item, kv_item = jnp.dtype(q_dtype).itemsize, jnp.dtype(kv_dtype).itemsize
    auto_g, auto_nbuf = _block_geometry(page_size, max_pages, hkv * d,
                                        kv_item)
    g_pages, nbuf = g_pages or auto_g, nbuf or auto_nbuf
    m = pad(m, _SUBLANES)
    gs = g_pages * page_size
    kv_buf = nbuf * 2 * gs * hkv * d * kv_item
    q_o_blocks = 2 * 2 * m * h * d * q_item
    carry = 2 * h * m * (pad(d, _LANES) + 2 * _LANES) * 4
    scores = 3 * m * pad(gs, _LANES) * 4
    return g_pages, nbuf, kv_buf + q_o_blocks + carry + scores


def _stacked_plan(h: int, hkv: int, d: int, page_size: int, max_pages: int,
                  q_dtype, kv_dtype, g_pages: int | None = None,
                  nbuf: int | None = None) -> tuple[int, int, int]:
    """:func:`_plan` for a walk at one row a lane with a KV head's query
    heads stacked (``ragged_paged_decode``, ``sparse_paged_decode``): the
    ``H / Hkv`` rows of one dot and a carry a KV head."""
    return _plan(h // hkv, hkv, hkv, d, page_size, max_pages, q_dtype,
                 kv_dtype, g_pages, nbuf)


def kernel_geometry_error(q_len: int, n_heads: int, n_kv_heads: int,
                          head_dim: int, page_size: int, max_pages: int,
                          q_dtype, kv_dtype, g_pages: int | None = None,
                          nbuf: int | None = None) -> str | None:
    """Why Mosaic cannot have the ragged kernels at this geometry, or None.

    The rule that selects and rejects the kernels — from shapes alone, so
    a caller learns it at construction and a real compile error is never
    caught to mean "use the other path".  ``q_len`` is the widest segment
    a dispatch carries: more than one row takes ``ragged_paged_attention``
    (a query head at a time), ONE row takes ``ragged_paged_decode`` (a KV
    head's ``g = n_heads / n_kv_heads`` query heads the rows of one dot).
    An engine runs decode steps whatever its widest segment is, so the
    one-row kernel is held to the rule at every ``q_len``.  Under a mesh
    pass the PER-SHARD head counts (``g`` is the same).  Constraints (each
    seen on a v5e, jax 0.9.0):

    - a page is DMA'd into a slice of the VMEM pipeline buffer, and
      Mosaic refuses a slice that is not whole tiles: the page row
      ``n_kv_heads * head_dim`` must be a multiple of 128 lanes and
      ``page_size`` a multiple of 8 sublanes;
    - one grid step's VMEM (:func:`_plan`) must fit the most the kernel
      may request; ``q_len`` and the heads per shard drive it in the rows
      kernel, ``g`` rows and ``n_kv_heads`` carries in the one-row kernel
      (:func:`_stacked_plan`);
    - a group's rows need NOT be whole tiles: the one-row kernel slices
      ``g`` rows out of its ``(H, D)`` query block and stores ``g`` rows
      of its output, and Mosaic takes groups of 1, 2, 4, 8 and 20 rows, in
      bf16 and in float32, as they are (compiled for a v5e and run on one,
      PR 40), so the wrapper pads nothing and no ``g`` is refused.
    """
    row = n_kv_heads * head_dim
    if row % _LANES:
        return (f"page row n_kv_heads*head_dim = {n_kv_heads}*{head_dim} = "
                f"{row} is not a multiple of {_LANES} lanes")
    if page_size % _SUBLANES:
        return (f"page_size {page_size} is not a multiple of {_SUBLANES} "
                "sublanes")
    limit = f"exceeds the {_VMEM_REQUEST_MAX >> 20} MiB it may request"
    need = _stacked_plan(n_heads, n_kv_heads, head_dim, page_size, max_pages,
                         q_dtype, kv_dtype, g_pages, nbuf)[2]
    if need > _VMEM_REQUEST_MAX:
        return (f"kernel VMEM {need >> 20} MiB for one row a lane, "
                f"{n_kv_heads} groups of {n_heads // n_kv_heads} rows x "
                f"{head_dim}, {limit} (shard the heads)")
    if q_len > 1:
        need = _plan(q_len, n_heads, n_kv_heads, head_dim, page_size,
                     max_pages, q_dtype, kv_dtype, g_pages, nbuf)[2]
        if need > _VMEM_REQUEST_MAX:
            return (f"kernel VMEM {need >> 20} MiB for q_len={q_len}, "
                    f"{n_heads} q heads x {head_dim} {limit} (shorten "
                    "the segment: prefill_chunk, or shard the heads)")
    return None


def _walk_scratch(nbuf: int, g_pages: int, kv_pool) -> list:
    """The scratch :func:`_page_walk` works in, ahead of a kernel's own:
    the staging buffer ``(nbuf, g_pages) + page`` (page-major: a slot is
    ``g_pages`` whole pages as the store keeps them, so a run of adjacent
    pages lands in it with ONE copy) and a DMA semaphore a page a slot (a
    run signals its first page's)."""
    return [pltpu.VMEM((nbuf, g_pages) + kv_pool.shape[2:], kv_pool.dtype),
            pltpu.SemaphoreType.DMA((nbuf, g_pages))]


def _table_runs(tables, g_pages: int):
    """``(B * n_blocks,)`` int32, the scalar-prefetch word :func:`_page_walk`
    reads a block: 1 where the block's ``g_pages`` table entries are an
    ASCENDING RUN of ids (``tables[b, j * g + i] == tables[b, j * g] + i``),
    so that its pages are adjacent in the store.  From the table alone, in
    the program that calls the kernel (one small fusion a program: the
    layers share it); whether a block's pages are all LIVE the kernel
    knows.  A block that reaches past the table's width is none."""
    b, mp = tables.shape
    n_blocks = -(-mp // g_pages)
    t = jnp.pad(tables, ((0, 0), (0, n_blocks * g_pages - mp)))
    t = t.reshape(b, n_blocks, g_pages)
    return (t[..., 1:] - t[..., :-1] == 1).all(axis=-1).astype(
        jnp.int32).reshape(-1)


def _page_walk(tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer,
               length, *, page_size: int, max_pages: int, g_pages: int,
               nbuf: int, also=None, first_block=None):
    """The walk over one lane's block table that every kernel of the family
    shares, whatever a page holds (K and V rows, or latent rows): starts
    the pipeline's prologue and returns ``(start_block, wait_block,
    live_blocks)``, the last the lane's count of blocks that hold a live
    page: the trip count of the caller's loop, so a block past the lane's
    length is never entered.  A block is ``g_pages`` table entries staged
    in slot ``slot`` of ``kv_buf``; every started DMA is waited exactly
    once; pages past ``length`` are neither fetched nor waited.

    A block whose ``g_pages`` entries are all live and an ASCENDING RUN of
    ids (``runs_ref``, :func:`_table_runs`) is ``g_pages`` adjacent pages
    of the layer, contiguous in HBM, and is fetched by ONE copy of
    ``kvpool_ref[layer, pid0 : pid0 + g_pages]`` into the slot; any other
    block (scattered ids, a lane's last partial block) by one copy a live
    page into the slot's strip ``gg``.  A page walk is bound by the count
    of its DMAs where a page is narrow (a 16 KiB page cost ~65 ns on a v5e,
    20 ns of bytes: PERF.md section 6, PR 55), and the pool hands a lane
    runs (:meth:`~tpulab.engine.kv_pool.PagedKVPool.allocate_pages`).  The
    TABLE the kernel is handed decides, block by block, and nothing else
    does; the bytes that land and where are the same either way.

    ``also(j, slot, go)`` rides a block that holds a live page: what else
    the caller stages a block (``go`` starts or waits a copy).

    ``first_block`` (a traced int32; None: block 0) is the LOWER bound of
    the walk, a window layer's: the blocks under it are neither fetched nor
    waited, the prologue starts there, and so does the caller's loop (``for
    j in [first_block, live_blocks)``, slot ``(j - first_block) % nbuf``).
    The first live block is fetched WHOLE, by its run word like any other:
    the keys in it that lie under the window are masked by the caller.

    The pages of a block and the blocks of the prologue are loops in the
    kernel, over the block's LIVE pages, not in Python: unrolled they were
    8 sites x ``g_pages`` DMA starts, each under a conditional of its own,
    and over half of what a kernel body costs to trace and to lower, which
    a step program pays on the host inside ``setup_s`` for every width it
    calls the kernel at (PR 33: chat's warm set-up 98 -> 67 s; the price is
    ~16 ns a page of scalar work in the kernel, PERF.md section 6)."""
    # live pages of the lane: page p is live iff p * page_size <= length
    n_pages = jnp.minimum(length // page_size + 1, max_pages)
    n_blocks = -(-max_pages // g_pages)         # as _table_runs counts them

    def block(j, slot, go):
        live = jnp.clip(n_pages - j * g_pages, 0, g_pages)
        full = live == g_pages
        # (a block past the table reads no word of it)
        run = jnp.logical_and(
            full, runs_ref[jnp.where(full, lane * n_blocks + j, 0)] != 0)
        first = lane * max_pages + j * g_pages

        @pl.when(run)
        def _run():
            go(pltpu.make_async_copy(
                kvpool_ref.at[layer, pl.ds(tables_ref[first], g_pages)],
                kv_buf.at[slot], sem.at[slot, 0]))

        def page(gg, _):
            go(pltpu.make_async_copy(
                kvpool_ref.at[layer, tables_ref[first + gg]],
                kv_buf.at[slot, gg], sem.at[slot, gg]))
        jax.lax.fori_loop(0, jnp.where(run, 0, live), page, None)
        if also is not None:
            pl.when(live > 0)(lambda: also(j, slot, go))

    def start_block(j, slot):
        block(j, slot, lambda copy: copy.start())

    def wait_block(j, slot):
        block(j, slot, lambda copy: copy.wait())

    # same deep prefetch pipeline as the single-query kernel (N-stage
    # slot rotation)
    start_block(0 if first_block is None else first_block, 0)

    def prologue(jj, _):
        # (a block past the lane's pages has no trip)
        start_block(jj if first_block is None else first_block + jj, jj)
    jax.lax.fori_loop(1, min(nbuf - 1, n_blocks), prologue, None)
    return start_block, wait_block, (n_pages + g_pages - 1) // g_pages


def _staged_rows(kv_buf, slot, which: int):
    """The staged block's rows ``(g_pages * S, row)`` of part ``which`` (K
    rows 0, V rows 1; a latent page's one part 0), page after page.  ``S``
    rows of a page are whole tiles of sublanes, so merging the pages is
    layout-free."""
    g, _parts, s, row = kv_buf.shape[1:]
    return kv_buf[slot, :, which].reshape(g * s, row)


def _zero_rows_past(kv_buf, slot, which: int, first_row, length):
    """Zero, in the staged block ``kv_buf[slot, :, which]`` itself, the
    rows at positions past ``length``: rows of pages not fetched hold stale
    VMEM (possibly NaN), the scores of such rows are masked, but as VALUES
    they ride a 0-weighted sum, and ``0 * NaN`` is NaN.  Only a lane's last
    live block holds such a row, so no other block pays for the pass."""
    g, _parts, s, _row = kv_buf.shape[1:]

    @pl.when(first_row + g * s > length + 1)
    def _zero():
        # a row's position, page-major as the block is staged
        at = (first_row
              + jax.lax.broadcasted_iota(jnp.int32, (g, s, 1), 0) * s
              + jax.lax.broadcasted_iota(jnp.int32, (g, s, 1), 1))
        blk = kv_buf[slot, :, which]
        kv_buf[slot, :, which] = jnp.where(at <= length, blk,
                                           jnp.zeros_like(blk))


def _stacked_carry(n_kv_heads: int, g: int, head_dim: int):
    """The start of a stacked one-row walk's carry: ``(running maximum (g,
    1), normaliser (g, 1), accumulator (g, D))`` a KV head, float32."""
    return tuple((jnp.full((g, 1), _NEG, jnp.float32),
                  jnp.zeros((g, 1), jnp.float32),
                  jnp.zeros((g, head_dim), jnp.float32))
                 for _ in range(n_kv_heads))


def _stacked_operands(q_ref, kv_buf, sm_scale: float):
    """``(q, dot_qk, dot_pv)`` of a stacked one-row walk: the lane's query
    block ``q_ref[0] (Hkv * g, D)`` scaled and rounded ONCE to the dtype
    :func:`mxu_operands` gives both products, and the two 2D dots at its
    precision (scores contract over D with the K block transposed, values
    in the standard orientation), float32 out."""
    dt, precision = mxu_operands(q_ref.dtype, kv_buf.dtype)
    q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(dt)
    dot_qk = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    dot_pv = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    return q, dot_qk, dot_pv


def _stacked_block(q, kblk, vblk, mask, carry, dot_qk, dot_pv):
    """One key block of a walk at ONE query row a lane, the ``g`` query
    heads of a KV head stacked into the rows of one dot: ``Hkv`` dot pairs
    a block, where a head at a time is ``H`` pairs of one live row each.
    The one body of the family's one-row walks (``ragged_paged_decode``,
    ``sparse_paged_decode``), which differ in the block's ``mask`` alone.

    ``q (Hkv * g, D)`` scaled and in the products' dtype, head ``hk``'s
    group rows ``hk * g`` on; ``kblk``, ``vblk (G*S, Hkv*D)`` the staged
    block; ``mask (1, G*S)`` bool, the keys of the block the row sees;
    ``carry`` as :func:`_stacked_carry` starts it.  Returns the carry."""
    g, d = carry[0][2].shape
    maskf = mask.astype(jnp.float32)
    out = []
    for hk, (m_c, l_c, acc_c) in enumerate(carry):
        cols = slice(hk * d, (hk + 1) * d)
        s = dot_qk(q[hk * g:(hk + 1) * g], kblk[:, cols])
        s = jnp.where(mask, s, _NEG)                          # (g, G*S)
        m_new = jnp.maximum(m_c, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_c - m_new)
        p = jnp.exp(s - m_new) * maskf
        out.append((m_new,
                    l_c * alpha + p.sum(axis=1, keepdims=True),
                    acc_c * alpha + dot_pv(p.astype(q.dtype),
                                           vblk[:, cols])))
    return tuple(out)


def _stacked_store(o_ref, carry):
    """The end of a stacked one-row walk: each group's accumulator over
    its normaliser into the group's rows of ``o_ref (1, Hkv * g, D)``."""
    for hk, (_m, l_c, acc_c) in enumerate(carry):
        g = acc_c.shape[0]
        o_ref[0, hk * g:(hk + 1) * g] = (
            acc_c / jnp.maximum(l_c, 1e-30)).astype(o_ref.dtype)


def _ragged_attn_kernel(layer_ref, tables_ref, runs_ref, qlens_ref, kvlens_ref,
                        q_ref, kvpool_ref, o_ref, kv_buf, sem, *,
                        page_size: int,
                        max_pages: int, n_heads: int, head_dim: int,
                        n_kv_heads: int, m_q: int, sm_scale: float,
                        g_pages: int, nbuf: int, window: int = 0):
    """One lane's ``M`` query rows against the lane's K/V pages, a head at
    a time.  Both products of a key block take their operands as
    :func:`mxu_operands` reads them from the store's dtype (a bf16 store:
    bf16 in one pass; a float32 store: float32 at ``HIGHEST``); the softmax
    statistics and the accumulator are float32 either way.

    ``window`` > 0 (a window layer): the row at position ``i`` sees key
    ``j`` iff ``i - window < j <= i``, each row its own bound; the walk
    starts at the block that holds the FIRST row's bound."""
    lane = pl.program_id(0)
    layer = layer_ref[0]                      # which layer's pages to walk
    qn = qlens_ref[lane]                      # valid query rows this lane
    kvn = kvlens_ref[lane]                    # context length incl. segment

    # a lane pays for the query rows it holds: one that holds none starts
    # no DMA, walks nothing and leaves its block of o_ref unwritten (the
    # caller reads no row of it)
    @pl.when(qn > 0)
    def _lane():
        # last visible position (a malformed kvn == 0 clamps to 0: the
        # walk always has a first page)
        length = jnp.maximum(kvn, 1) - 1
        start = kvn - qn                      # first query's position
        h, d = n_heads, head_dim
        hkv = n_kv_heads
        g = h // hkv                          # GQA group size (1 = MHA)
        gs = g_pages * page_size              # KV rows per block

        # both products take their operands in ``dt``: the scaled query
        # rounded to it here, once, the probabilities a block
        dt, precision = mxu_operands(q_ref.dtype, kv_buf.dtype)
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(dt)  # (M, H*D)
        # flash-style 2D dots only (the Mosaic-safe subset): scores contract
        # over D with the K block transposed, values with the standard
        # orientation
        dot_qk = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dot_pv = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

        # a window layer's walk starts at the block of its first row's
        # oldest visible key (no bound: block 0, the walk it always was)
        j0 = (jnp.maximum(start - window + 1, 0) // gs if window else None)
        start_block, wait_block, live_blocks = _page_walk(
            tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer, length,
            page_size=page_size, max_pages=max_pages, g_pages=g_pages,
            nbuf=nbuf, first_block=j0)

        # per-query-row positions/validity are loop-invariant
        qrow = jax.lax.broadcasted_iota(jnp.int32, (m_q, gs), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (m_q, gs), 1)
        qpos = start + qrow                   # (M, G*S) per-row position
        row_valid = qrow < qn

        def body(j, carry):
            # (a window layer's slots count from its first block)
            at = j - j0 if window else j
            slot = jax.lax.rem(at, nbuf)
            wait_block(j, slot)
            # (a block past the lane's pages has no trip)
            start_block(j + nbuf - 1, jax.lax.rem(at + nbuf - 1, nbuf))

            _zero_rows_past(kv_buf, slot, 1, j * gs, length)
            # as stored (an fp8 block upcast): no float32 copy
            kblk = _staged_rows(kv_buf, slot, 0).astype(dt)  # (G*S, Hkv*D)
            vblk = _staged_rows(kv_buf, slot, 1).astype(dt)
            kpos = j * gs + col
            mask = jnp.logical_and(kpos <= qpos, row_valid)      # (M, G*S)
            if window:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            maskf = mask.astype(jnp.float32)
            out = []
            for hh in range(h):
                m_c, l_c, acc_c = carry[hh]
                hk = hh // g                      # compact-form KV head
                k_h = kblk[:, hk * d:(hk + 1) * d]              # (G*S, D)
                v_h = vblk[:, hk * d:(hk + 1) * d]
                q_h = q[:, hh * d:(hh + 1) * d]                 # (M, D)
                s = dot_qk(q_h, k_h)                            # (M, G*S)
                s = jnp.where(mask, s, _NEG)
                m_new = jnp.maximum(m_c, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_c - m_new)                    # (M, 1)
                p = jnp.exp(s - m_new) * maskf
                l_new = l_c * alpha + p.sum(axis=1, keepdims=True)
                acc_new = acc_c * alpha + dot_pv(p.astype(dt), v_h)
                out.append((m_new, l_new, acc_new))
            return tuple(out)

        init = tuple((jnp.full((m_q, 1), _NEG, jnp.float32),
                      jnp.zeros((m_q, 1), jnp.float32),
                      jnp.zeros((m_q, d), jnp.float32)) for _ in range(h))
        final = jax.lax.fori_loop(j0 if window else 0, live_blocks, body,
                                  init)
        for hh in range(h):
            _m, l_c, acc_c = final[hh]
            o_ref[0, :, hh * d:(hh + 1) * d] = (
                acc_c / jnp.maximum(l_c, 1e-30)).astype(o_ref.dtype)


def _ragged_decode_kernel(layer_ref, tables_ref, runs_ref, qlens_ref,
                          kvlens_ref, q_ref, kvpool_ref, o_ref, kv_buf, sem, *,
                          page_size: int, max_pages: int, n_kv_heads: int,
                          sm_scale: float, g_pages: int, nbuf: int,
                          window: int = 0):
    """One lane's ONE query row against the lane's K/V pages, the query
    heads of a KV head the rows of one dot (:func:`_stacked_block`): the
    rows kernel at ``M = 1`` pushed every K and V block through the MXU
    ``H / Hkv`` times, a padded tile of rows for the one that counts.
    ``q_ref``, ``o_ref (1, Hkv * g, D)``.  At one row the mask of a block
    is positional: every key at or before the lane's last position, and
    with ``window`` > 0 (a window layer) no key ``window`` or more behind
    it: the walk starts at the block of the oldest visible key."""
    lane = pl.program_id(0)
    layer = layer_ref[0]

    @pl.when(qlens_ref[lane] > 0)        # see _ragged_attn_kernel
    def _lane():
        length = jnp.maximum(kvlens_ref[lane], 1) - 1
        gs = g_pages * page_size
        if window:
            first = jnp.maximum(length - window + 1, 0)  # oldest visible key
        j0 = first // gs if window else None
        start_block, wait_block, live_blocks = _page_walk(
            tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer, length,
            page_size=page_size, max_pages=max_pages, g_pages=g_pages,
            nbuf=nbuf, first_block=j0)

        q, dot_qk, dot_pv = _stacked_operands(q_ref, kv_buf, sm_scale)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, gs), 1)

        def body(j, carry):
            # (a window layer's slots count from its first block)
            at = j - j0 if window else j
            slot = jax.lax.rem(at, nbuf)
            wait_block(j, slot)
            # (a block past the lane's pages has no trip)
            start_block(j + nbuf - 1, jax.lax.rem(at + nbuf - 1, nbuf))
            _zero_rows_past(kv_buf, slot, 1, j * gs, length)
            kblk = _staged_rows(kv_buf, slot, 0).astype(q.dtype)
            vblk = _staged_rows(kv_buf, slot, 1).astype(q.dtype)
            mask = j * gs + col <= length
            if window:
                mask = jnp.logical_and(mask, j * gs + col >= first)
            return _stacked_block(q, kblk, vblk, mask, carry, dot_qk, dot_pv)

        _stacked_store(o_ref, jax.lax.fori_loop(
            j0 if window else 0, live_blocks, body,
            _stacked_carry(n_kv_heads, q.shape[0] // n_kv_heads,
                           q.shape[1])))


def _ragged_decode(q, kv_pool, layer, tables, q_lens, kv_lens,
                   interpret: bool, g_pages, nbuf, window: int = 0):
    """:func:`_ragged_attn` at one query row a lane: ``q (B, 1, H, D)``
    through the kernel ``ragged_paged_decode``."""
    b, _one, h, d = q.shape
    page_size, row = kv_pool.shape[3], kv_pool.shape[4]
    hkv = row // d
    max_pages = tables.shape[1]
    g_pages, nbuf, need = _stacked_plan(h, hkv, d, page_size, max_pages,
                                        q.dtype, kv_pool.dtype, g_pages, nbuf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # layer, tables (flat), runs, q/kv_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda lane, *_: (lane, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # KV pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda lane, *_: (lane, 0, 0)),
        scratch_shapes=_walk_scratch(nbuf, g_pages, kv_pool),
    )
    kernel = functools.partial(
        _ragged_decode_kernel, page_size=page_size, max_pages=max_pages,
        n_kv_heads=hkv, sm_scale=1.0 / np.sqrt(d), g_pages=g_pages,
        nbuf=nbuf, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            max(_VMEM_SCOPED_DEFAULT, need * 3 // 2), _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="ragged_paged_decode",
    )(layer, tables.reshape(-1), _table_runs(tables, g_pages), q_lens,
      kv_lens, q.reshape(b, h, d), kv_pool)
    return out.reshape(b, 1, h, d)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "g_pages", "nbuf", "window"))
def _ragged_attn(q, kv_pool, layer, tables, q_lens, kv_lens, interpret: bool,
                 g_pages: int | None = None, nbuf: int | None = None,
                 window: int = 0):
    b, m, h, d = q.shape
    page_size, row = kv_pool.shape[3], kv_pool.shape[4]
    hkv = row // d
    if hkv * d != row:
        raise ValueError(f"page row {row} is not a whole number of heads "
                         f"of {d}")
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    max_pages = tables.shape[1]
    if not interpret:
        err = kernel_geometry_error(m, h, hkv, d, page_size, max_pages,
                                    q.dtype, kv_pool.dtype, g_pages, nbuf)
        if err:
            raise ValueError(f"ragged_paged_attention: {err}")
    if m == 1:
        # one row a lane (a decode step, a round's decode rows): the heads
        # of a KV head stack into one dot's rows.  The shape decides,
        # nothing else
        return _ragged_decode(q, kv_pool, layer, tables, q_lens, kv_lens,
                              interpret, g_pages, nbuf, window)
    # the pool goes in as it is stored — a reshape or a slice of it ahead
    # of the call would be a copy of a layer of the pool on every call (a
    # pallas_call operand is not fused into); queries as (B, M, H*D)
    q2 = q.reshape(b, m, h * d)
    g_pages, nbuf, need = _plan(m, h, hkv, d, page_size, max_pages, q.dtype,
                                kv_pool.dtype, g_pages, nbuf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # layer, tables (flat), runs, q/kv_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, m, h * d), lambda lane, *_: (lane, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # KV pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, m, h * d),
                               lambda lane, *_: (lane, 0, 0)),
        scratch_shapes=_walk_scratch(nbuf, g_pages, kv_pool),
    )
    kernel = functools.partial(
        _ragged_attn_kernel, page_size=page_size, max_pages=max_pages,
        n_heads=h, head_dim=d, n_kv_heads=hkv, m_q=m,
        sm_scale=1.0 / np.sqrt(d), g_pages=g_pages, nbuf=nbuf,
        window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, m, h * d), q.dtype),
        # wide segments (a 256-token chunk over 16 heads) pass the 16 MiB
        # default; ask for what the shapes need, with headroom for
        # Mosaic's own temporaries
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            max(_VMEM_SCOPED_DEFAULT, need * 3 // 2), _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="ragged_paged_attention",
    )(layer, tables.reshape(-1), _table_runs(tables, g_pages), q_lens,
      kv_lens, q2, kv_pool)
    return out.reshape(b, m, h, d)


def ragged_paged_attention(q, kv_pool, layer, tables, q_lens, kv_lens,
                           mesh=None, model_axis: str = "model",
                           interpret: bool | None = None,
                           g_pages: int | None = None,
                           nbuf: int | None = None, window: int = 0):
    """Ragged paged attention over per-lane ``(query_len, kv_len)``
    segments (MHA or grouped-query), on one layer of the page store.

    q (B, M, Hq, D) — up to M query tokens per lane, left-packed: lane
    b's valid queries are ``q[b, :q_lens[b]]``, query j sitting at
    global position ``kv_lens[b] - q_lens[b] + j`` and attending every
    context position <= its own (the gather-after-scatter contract: the
    segment's K/V are already resident in the pool);
    kv_pool (L, P, 2, S, Hkv*D) — the WHOLE page store as
    :class:`~tpulab.engine.kv_pool.PagedKVPool` keeps it (axis 2 = K/V
    adjacent in HBM: one DMA a page, or one a block of adjacent pages; a
    row is the KV heads side by
    side, ``Hkv = row // D``, and ``Hkv < Hq`` selects GQA).  The kernel
    reads pages straight out of it: never hand it ``kv_pool[layer]`` or
    a reshape, which XLA would materialise as a copy of a layer of the
    pool on every call;
    layer — which layer's pages to walk (int, or a traced int32 scalar:
    it rides the scalar prefetch, the page DMAs read
    ``kv_pool[layer, page]``);
    tables (B, MP) int32 page ids (padded rows point at scratch page 0);
    q_lens (B,) int32 — segment length per lane (0 = the lane is skipped:
    nothing of it is read and its output rows are UNWRITTEN, whatever the
    buffer held, so the caller must read none of them);
    kv_lens (B,) int32 — context length per lane INCLUDING the segment
    (NOTE: a count, not the last position — ``q_lens == 1,
    kv_lens == position + 1`` is the single-query decode shape).

    ``mesh=`` shards the walk over the KV heads via ``shard_map`` (page
    rows per :func:`tpulab.parallel.sharding.kv_pool_sharding`:
    contiguous head groups of the row; q/output on the heads dim,
    tables/lengths/layer replicated) so the kernel compiles inside the
    engine's tensor-parallel jits.
    ``g_pages``/``nbuf`` override the auto block geometry.
    ``window`` > 0 makes the layer a WINDOW layer: the query at position
    ``i`` sees key ``j`` iff ``i - window < j <= i``; the walk starts at the
    block that holds the first row's oldest visible key, so ``tables`` need
    hold live ids from that block on only (one device, no ``mesh``).
    ``M == 1`` runs the kernel ``ragged_paged_decode`` (a KV head's query
    heads the rows of one dot), ``M > 1`` ``ragged_paged_attention`` (a
    query head at a time): the module docstring says why.
    Numerics: both matrix products of a key block follow
    :func:`mxu_operands` (a store under 32 bits: the query's dtype in one
    pass, float32 accumulation; a float32 store: ``HIGHEST``).
    Returns (B, M, Hq, D).
    """
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tables = tables.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    if mesh is None:
        return _ragged_attn(q, kv_pool, layer, tables, q_lens, kv_lens,
                            interpret, g_pages=g_pages, nbuf=nbuf,
                            window=window)
    if window:
        raise NotImplementedError("a window layer's walk is not sharded "
                                  "(mesh= with window=)")
    from jax.sharding import PartitionSpec as P

    n_model = dict(mesh.shape)[model_axis]
    h, hkv = q.shape[2], kv_pool.shape[4] // q.shape[3]
    if h % n_model or hkv % n_model:
        raise ValueError(
            f"query heads ({h}) and KV heads ({hkv}) must divide the "
            f"mesh {model_axis!r} axis ({n_model}) — the ragged kernel "
            "shards on the heads dim")
    body = functools.partial(_ragged_attn, interpret=interpret,
                             g_pages=g_pages, nbuf=nbuf)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, model_axis, None),
                  P(None, None, None, None, model_axis), P(None),
                  P(None, None), P(None), P(None)),
        out_specs=P(None, None, model_axis, None),
        check_vma=False,   # pallas_call has no shard_map replication rule
    )(q, kv_pool, layer, tables, q_lens, kv_lens)


# ---------------------------------------------------------------------------
# latent pages (multi-head latent attention, absorbed form)
# ---------------------------------------------------------------------------

#: what a tile of a latent kernel may plan (:func:`_latent_plan`), little
#: over half of ``_VMEM_REQUEST_MAX``: Mosaic's temporaries come on top.  The
#: widest tile within it is taken: a tile walks its lane's pages once
#: whatever it holds, and a key block staged once is the weights of ONE pair
#: of products for all the tile's rows
_LATENT_TILE_BUDGET = 56 << 20


class _LatentPlan(NamedTuple):
    """What one grid step of a latent kernel holds (:func:`_latent_plan`)."""
    heads_tile: int     # whole heads a tile: ``n_heads / heads_tile`` walks
    rows: int           # their ``heads_tile * m`` rows, padded to a tile
    g_pages: int
    nbuf: int
    vmem_bytes: int


def _latent_plan(m: int, h: int, row: int, v_width: int, page_size: int,
                 max_pages: int, q_dtype, kv_dtype,
                 heads_tile: int | None = None) -> _LatentPlan:
    """:func:`_plan` for the latent kernels, and the tile it is the plan of.

    A lane's rows are ``(head, token)`` pairs, head-major, and every head
    attends the same key rows, so whole heads stack into the rows of one
    product and a tile is ``heads_tile`` of them: the most whose bytes stay
    within ``_LATENT_TILE_BUDGET`` (at least one; ``heads_tile`` pins it),
    as :func:`_block_geometry` picks ``g_pages``.  At ONE row a lane that
    is every head of the lane at the published widths.  The bytes: the page
    pipeline, the double-buffered query and output blocks, the running
    maximum, the normaliser (a 128-lane tile a row each) and the
    accumulator in float32 (at one row a loop's carry, live twice across a
    step; at more in scratch, beside the tile's scaled queries), and the
    score tiles."""
    q_item, kv_item = jnp.dtype(q_dtype).itemsize, jnp.dtype(kv_dtype).itemsize
    # at more than one row the scaled queries are a copy worth counting
    scaled = (jnp.dtype(mxu_operands(q_dtype, kv_dtype)[0]).itemsize
              if m > 1 else 0)
    g_pages, nbuf = _block_geometry(page_size, max_pages, row, kv_item)
    gs = g_pages * page_size

    def rows(heads):
        return -(-heads * m // 16) * 16           # a packed bf16 tile

    def need(r):
        return (nbuf * gs * row * kv_item
                + 2 * r * (row + v_width) * q_item + r * row * scaled
                + (2 if m == 1 else 1) * r * (v_width + 2 * _LANES) * 4
                + 3 * r * -(-gs // _LANES) * _LANES * 4)

    if heads_tile is None:
        heads_tile = max((c for c in range(1, h + 1) if h % c == 0
                          and need(rows(c)) <= _LATENT_TILE_BUDGET),
                         default=1)
    r = rows(heads_tile)
    return _LatentPlan(heads_tile, r, g_pages, nbuf, need(r))


def latent_tile(q_len: int, n_heads: int, row: int, v_width: int,
                page_size: int, max_pages: int, q_dtype, kv_dtype) -> dict:
    """The tile :func:`_latent_plan` gives a lane of ``q_len`` rows, as a
    person reads it: ``heads`` a tile, ``walks`` of the lane's pages (one a
    tile), ``vmem_bytes`` a grid step."""
    plan = _latent_plan(q_len, n_heads, row, v_width, page_size, max_pages,
                        q_dtype, kv_dtype)
    return {"heads": plan.heads_tile, "walks": n_heads // plan.heads_tile,
            "vmem_bytes": plan.vmem_bytes}


def latent_geometry_error(q_len: int, n_heads: int, row: int, v_width: int,
                          page_size: int, max_pages: int, q_dtype, kv_dtype,
                          heads_tile: int | None = None) -> str | None:
    """:func:`kernel_geometry_error` for latent pages: whole-tile page
    DMAs, whole-tile value columns, and a grid step that fits VMEM
    (:func:`_latent_plan`: the tile it picks, or one of ``heads_tile``
    heads; the refusal names the widest tile that does fit)."""
    if row % _LANES or v_width % _LANES:
        return (f"latent row {row} and its value width {v_width} must be "
                f"multiples of {_LANES} lanes")
    if page_size % _SUBLANES:
        return (f"page_size {page_size} is not a multiple of {_SUBLANES} "
                "sublanes")

    def plan(heads):
        return _latent_plan(q_len, n_heads, row, v_width, page_size,
                            max_pages, q_dtype, kv_dtype, heads)
    tile = plan(heads_tile)
    if tile.vmem_bytes > _VMEM_REQUEST_MAX:
        fits = max((c for c in range(1, tile.heads_tile) if n_heads % c == 0
                    and plan(c).vmem_bytes <= _VMEM_REQUEST_MAX), default=0)
        return (f"kernel VMEM {tile.vmem_bytes >> 20} MiB for q_len={q_len} "
                f"at {tile.heads_tile} heads a tile exceeds the "
                f"{_VMEM_REQUEST_MAX >> 20} MiB it may request ("
                + (f"the widest tile that fits holds {fits}" if fits else
                   "no tile fits: shorten the segment, prefill_chunk") + ")")
    return None


def _latent_attn_kernel(layer_ref, tables_ref, runs_ref, qlens_ref, kvlens_ref,
                        q_ref, kvpool_ref, o_ref, kv_buf, sem, *,
                        page_size: int,
                        max_pages: int, m_q: int, rows: int, v_width: int,
                        sm_scale: float, g_pages: int, nbuf: int):
    """One lane's tile of stacked heads against the lane's latent pages.

    ``q_ref (1, rows, W)``: row ``r`` is query token ``r % m_q`` of some
    head — every head attends the SAME ``W``-wide key row (absorbed MLA),
    so the heads of a lane are rows of one dot, and the value is the first
    ``v_width`` columns of the same staged row.  Both products take their
    operands as :func:`mxu_operands` reads them from the store's dtype: the
    staged block goes to both as stored, the probabilities rounded to it.

    :func:`_latent_attn` gives it the calls at ONE row a lane (a decode
    step, a round's decode rows: every head of the lane one tile); the body
    is PR 28's for any ``m_q``, kept to the letter so that those calls keep
    their program.  More rows a lane go to :func:`_latent_rows_kernel`."""
    lane = pl.program_id(0)
    layer = layer_ref[0]
    qn = qlens_ref[lane]
    kvn = kvlens_ref[lane]

    @pl.when(qn > 0)                     # see _ragged_attn_kernel
    def _lane():
        length = jnp.maximum(kvn, 1) - 1
        start = kvn - qn
        gs = g_pages * page_size

        dt, precision = mxu_operands(q_ref.dtype, kv_buf.dtype)
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(dt)   # (R, W)
        dot_qk = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dot_pv = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

        start_block, wait_block, live_blocks = _page_walk(
            tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer, length,
            page_size=page_size, max_pages=max_pages, g_pages=g_pages,
            nbuf=nbuf)

        qrow = jax.lax.broadcasted_iota(jnp.int32, (rows, gs), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, gs), 1)
        # token index of a stacked row: heads are m_q rows apart
        qtok = (qrow & (m_q - 1) if m_q & (m_q - 1) == 0
                else jax.lax.rem(qrow, m_q))
        qpos = start + qtok
        row_valid = qtok < qn

        def body(j, carry):
            m_c, l_c, acc_c = carry
            slot = jax.lax.rem(j, nbuf)
            wait_block(j, slot)
            # (a block past the lane's pages has no trip)
            start_block(j + nbuf - 1, jax.lax.rem(j + nbuf - 1, nbuf))

            # the one staged row is key and value
            _zero_rows_past(kv_buf, slot, 0, j * gs, length)
            blk = _staged_rows(kv_buf, slot, 0).astype(dt)       # (G*S, W)
            mask = jnp.logical_and(j * gs + col <= qpos, row_valid)
            s = jnp.where(mask, dot_qk(q, blk), _NEG)            # (R, G*S)
            m_new = jnp.maximum(m_c, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_c - m_new)
            p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
            return (m_new, l_c * alpha + p.sum(axis=1, keepdims=True),
                    acc_c * alpha + dot_pv(p.astype(dt), blk[:, :v_width]))

        init = (jnp.full((rows, 1), _NEG, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, v_width), jnp.float32))
        _m, l_c, acc_c = jax.lax.fori_loop(0, live_blocks, body, init)
        o_ref[0] = (acc_c / jnp.maximum(l_c, 1e-30)).astype(o_ref.dtype)


def _latent_rows_kernel(layer_ref, tables_ref, runs_ref, qlens_ref, kvlens_ref,
                        q_ref, kvpool_ref, o_ref, kv_buf, sem, m_ref, l_ref,
                        acc_ref, *, page_size: int, max_pages: int, m_q: int,
                        v_width: int, sm_scale: float, g_pages: int,
                        nbuf: int):
    """One lane's tile of stacked heads at MORE than one row a head,
    against the lane's latent pages: :func:`_latent_attn_kernel`'s products
    and online softmax, block for block, for a tile of thousands of rows.

    ``q_ref (1, rows, W)``: whole heads of ``m_q`` rows each.  The lane's
    pages are walked ONCE for the tile, and a staged key block
    is the weights of one pair of products for all its rows.  The running
    maximum, normaliser and accumulator live in VMEM scratch (``m_ref``,
    ``l_ref (rows, 1)``, ``acc_ref (rows, v_width)``): as a loop's carry
    Mosaic took 19 s to compile a tile of 4,096 rows and ran it a fifth
    slower (PERF.md section 6, PR 47).

    A block's mask is positional alone, and it is applied once, to the
    scores.  ``kv_lens`` counts the segment, so the chunk's first position
    ``kvn - qn`` is not negative and every row sees key 0: its running
    maximum is a score from the first block on, a masked score's
    ``exp(-1e30 - m)`` is exactly 0, and the probabilities need no second
    multiply by the mask.  Rows past ``qn`` are zeroed where the tile is
    stored."""
    lane = pl.program_id(0)
    layer = layer_ref[0]
    qn = qlens_ref[lane]
    kvn = kvlens_ref[lane]

    @pl.when(qn > 0)                     # see _ragged_attn_kernel
    def _lane():
        length = jnp.maximum(kvn, 1) - 1
        start = kvn - qn
        gs = g_pages * page_size
        rows = q_ref.shape[1]
        dt, precision = mxu_operands(q_ref.dtype, kv_buf.dtype)
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(dt)   # (R, W)
        dot_qk = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dot_pv = functools.partial(
            jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        start_block, wait_block, live_blocks = _page_walk(
            tables_ref, runs_ref, kvpool_ref, kv_buf, sem, lane, layer, length,
            page_size=page_size, max_pages=max_pages, g_pages=g_pages,
            nbuf=nbuf)

        def token(shape):
            # token index of a stacked row: heads are m_q rows apart
            r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            return (r & (m_q - 1) if m_q & (m_q - 1) == 0
                    else jax.lax.rem(r, m_q))
        # key column - query token: a row sees key ``j * gs + col`` iff
        # this is at most ``start - j * gs``
        ahead = (jax.lax.broadcasted_iota(jnp.int32, (rows, gs), 1)
                 - token((rows, gs)))

        def block(j, _):
            slot = jax.lax.rem(j, nbuf)
            wait_block(j, slot)
            # (a block past the lane's pages has no trip)
            start_block(j + nbuf - 1, jax.lax.rem(j + nbuf - 1, nbuf))

            # the one staged row is key and value
            _zero_rows_past(kv_buf, slot, 0, j * gs, length)
            blk = _staged_rows(kv_buf, slot, 0).astype(dt)       # (G*S, W)
            s = jnp.where(ahead <= start - j * gs, dot_qk(q, blk),
                          _NEG)                                  # (R, G*S)
            m_c = m_ref[...]
            m_new = jnp.maximum(m_c, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_c - m_new)
            p = jnp.exp(s - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + dot_pv(
                p.astype(dt), blk[:, :v_width])

        jax.lax.fori_loop(0, live_blocks, block, None)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = jnp.where(token((rows, 1)) < qn, out, 0.0).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("v_width", "sm_scale",
                                             "interpret", "heads_tile"))
def _latent_attn(q, kv_pool, layer, tables, q_lens, kv_lens, v_width: int,
                 sm_scale: float, interpret: bool,
                 heads_tile: int | None = None):
    b, m, h, w = q.shape
    page_size, row = kv_pool.shape[3], kv_pool.shape[4]
    max_pages = tables.shape[1]
    if kv_pool.shape[2] != 1 or w > row:
        raise ValueError(f"latent attention needs a latent page store "
                         f"(L, P, 1, S, row >= {w}); got {kv_pool.shape}")
    if not interpret:
        err = latent_geometry_error(m, h, row, v_width, page_size, max_pages,
                                    q.dtype, kv_pool.dtype, heads_tile)
        if err:
            raise ValueError(f"ragged_latent_attention: {err}")
    hb, rows, g_pages, nbuf, need = _latent_plan(
        m, h, row, v_width, page_size, max_pages, q.dtype, kv_pool.dtype,
        heads_tile)
    n_tiles = h // hb
    # heads stack into rows, head-major, a tile of whole heads; query
    # columns padded to the (zero-padded) page row
    qs = q.transpose(0, 2, 1, 3).reshape(b, n_tiles, hb * m, w)
    kw = dict(page_size=page_size, max_pages=max_pages, m_q=m,
              v_width=v_width, sm_scale=sm_scale, g_pages=g_pages, nbuf=nbuf)
    scratch = _walk_scratch(nbuf, g_pages, kv_pool)
    if m == 1:
        # one row a lane (a decode step, a round's decode rows): the shape
        # decides, nothing else
        kernel = functools.partial(_latent_attn_kernel, rows=rows, **kw)
    else:
        kernel = functools.partial(_latent_rows_kernel, **kw)
        scratch += [pltpu.VMEM((rows, 1), jnp.float32),
                    pltpu.VMEM((rows, 1), jnp.float32),
                    pltpu.VMEM((rows, v_width), jnp.float32)]
    qs = jnp.pad(qs, ((0, 0), (0, 0), (0, rows - hb * m), (0, row - w)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # layer, tables (flat), runs, q/kv_lens
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, row), lambda lane, t, *_: (lane, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # page store stays in HBM
        ],
        out_specs=pl.BlockSpec((1, rows, v_width),
                               lambda lane, t, *_: (lane, t, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_tiles * rows, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            max(_VMEM_SCOPED_DEFAULT, need * 3 // 2), _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="ragged_latent_attention",
    )(layer, tables.reshape(-1), _table_runs(tables, g_pages), q_lens,
      kv_lens, qs.reshape(b, n_tiles * rows, row), kv_pool)
    out = out.reshape(b, n_tiles, rows, v_width)[:, :, :hb * m]
    return out.reshape(b, h, m, v_width).transpose(0, 2, 1, 3)


def ragged_latent_attention(q, kv_pool, layer, tables, q_lens, kv_lens, *,
                            v_width: int, sm_scale: float,
                            interpret: bool | None = None):
    """Ragged paged attention over *latent* pages: every query head of a
    lane attends one shared key row a position, and the value is the first
    ``v_width`` columns of the same row (MLA in the absorbed form).

    q (B, M, H, W) — absorbed queries ``[q_nope W_uk ; q_rope]``, segments
    as in :func:`ragged_paged_attention`;
    kv_pool (L, P, 1, S, row) — the latent page store, ``row >= W`` (the
    row padded with zeros to whole lanes);
    ``sm_scale`` — the softmax scale of the *published* head width
    (``1 / sqrt(qk_nope + qk_rope)``), not of ``W``.
    Same block tables, ``layer`` word, ``q_lens``/``kv_lens`` contract,
    page walk and operand rule (:func:`mxu_operands`) as the K/V kernel.
    Returns (B, M, H, v_width)."""
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _latent_attn(q, kv_pool, jnp.asarray(layer, jnp.int32).reshape(1),
                        tables.astype(jnp.int32), q_lens.astype(jnp.int32),
                        kv_lens.astype(jnp.int32), v_width=int(v_width),
                        sm_scale=float(sm_scale), interpret=interpret)
