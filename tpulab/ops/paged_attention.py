"""Pallas ragged paged-attention decode kernel.

The decode-attention shape from the TPU serving literature (ragged paged
attention): each lane attends one query token against its own block table of
KV pages.  The XLA fallback in :func:`tpulab.engine.paged.paged_decode_step`
*gathers* every lane's pages into a dense (B, MP*S, H, D) tensor — correct
but materializes the gather in HBM; this kernel instead walks the block
table per lane, DMA-ing pages from the pool (HBM) into VMEM scratch and
accumulating softmax online — O(block) VMEM, no gather materialization,
and dead pages (beyond the lane's length) are skipped by predication.

Two levels of batching keep the walk off the critical path:

- **Fused page layout** (P, 2, S, Hkv*D): a page's K and V rows are
  adjacent in HBM and arrive in ONE DMA — half the issue count of
  separate K/V pools.
- **Multi-page blocks** (round 3): the loop iterates over blocks of
  ``G`` pages, issuing the block's G page-DMAs back-to-back and running
  ONE compute step over the concatenated (G*S, Hkv*D) rows.  A
  page-per-iteration walk at serving geometries (S=16..32) is bound by
  per-iteration fixed costs — DMA issue, semaphore waits, loop control,
  and the softmax-rescale micro-dots, each amortized over only S rows.
  Blocks of G pages cut the iteration count by G and feed the MXU
  ~G*S-row matmuls instead of S-row slivers.  Block DMAs additionally
  ride an ``nbuf``-deep slot-rotation prefetch pipeline (iteration j
  waits slot ``j % nbuf``, computes, then refills the previous
  iteration's slot with block ``j + nbuf - 1``), keeping
  ``(nbuf-1) * G`` page copies in flight.

Scalar-prefetched block tables/lengths drive the page DMAs (the
PrefetchScalarGridSpec pattern).  ``interpret=True`` (automatic off TPU)
runs the same kernel on CPU for hermetic tests.

Mosaic-compatibility note: every dot in the kernel is a plain 2D matmul.
Per-head contraction is expressed through a loop-invariant one-hot
head-selector matrix ((H*D, H)) instead of batched ``dot_general``
dimension numbers — batched dots fail to round-trip through the TPU
compile service's MLIR text serialization, and middle-dimension DMA
slices (the per-head-DMA alternative) require 128-lane alignment that
head_dim=64 models don't satisfy.  Pages are therefore staged as fused
(2, page_size, Hkv*D) K/V blocks (a free, contiguous reshape at the
caller).
"""

from __future__ import annotations

import functools

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


def _paged_attn_kernel(tables_ref, lengths_ref, q_ref, kvpool_ref,
                       o_ref, kv_buf, sem, *, page_size: int,
                       max_pages: int, n_heads: int, head_dim: int,
                       n_kv_heads: int, sm_scale: float, precision,
                       g_pages: int, nbuf: int):
    lane = pl.program_id(0)
    length = lengths_ref[lane]                    # tokens visible (incl. current)
    h, d, hd = n_heads, head_dim, n_heads * head_dim
    hkv, hd_kv = n_kv_heads, n_kv_heads * head_dim
    g = h // hkv                                  # GQA group size (1 = MHA)
    gs = g_pages * page_size                      # KV rows per block
    n_blocks = (max_pages + g_pages - 1) // g_pages

    q = q_ref[0].astype(jnp.float32) * sm_scale    # (1, H*D)
    # loop-invariant head selectors (hoisted out of the block loop by the
    # compiler): sel (H*D, H) sums a row's per-head D-blocks; sel_t expands
    # per-head scalars back across their D-block
    blk = jax.lax.broadcasted_iota(jnp.int32, (hd, h), 0) // d
    col = jax.lax.broadcasted_iota(jnp.int32, (hd, h), 1)
    sel = (blk == col).astype(jnp.float32)         # (H*D, H)
    blk_t = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 1) // d
    row_t = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0)
    sel_t = (blk_t == row_t).astype(jnp.float32)   # (H, H*D)
    if g > 1:
        # GQA: expansion one-hot (Hkv*D, H*D) broadcasting each KV head's
        # D-block across its g query heads (exact: one 1.0 per column).
        # Pages stage and DMA in the COMPACT Hkv form — the bandwidth win —
        # and expand on the fly in VMEM via one matmul per block.
        r_i = jax.lax.broadcasted_iota(jnp.int32, (hd_kv, hd), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (hd_kv, hd), 1)
        expand = jnp.logical_and(r_i // d == (c_i // d) // g,
                                 r_i % d == c_i % d).astype(jnp.float32)
    # score dot: operands are pool/query data — precision follows the pool
    # dtype (bf16 data carries no extra bits for HIGHEST to preserve).
    # selector-expansion dots: operands are f32 softmax intermediates
    # (p, alpha, l) — ALWAYS HIGHEST, or the running rescale would round
    # to bf16 on every block and compound across the context walk.
    dot2 = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    dot_sel = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    def page_live(p):
        return p * page_size <= length

    # one block = g_pages fused-page DMAs issued back-to-back into the
    # slot's per-page strips; dest strip index is STATIC (python g), only
    # the source page id is dynamic — g_pages unrolled copies per block
    def start_block(j, slot):
        for gg in range(g_pages):
            p_idx = j * g_pages + gg

            @pl.when(jnp.logical_and(p_idx < max_pages, page_live(p_idx)))
            def _start(gg=gg, p_idx=p_idx):
                page = tables_ref[lane * max_pages + p_idx]
                pltpu.make_async_copy(
                    kvpool_ref.at[page],
                    kv_buf.at[slot, :, pl.ds(gg * page_size, page_size)],
                    sem.at[slot, gg]).start()

    def wait_block(j, slot):
        for gg in range(g_pages):
            p_idx = j * g_pages + gg

            @pl.when(jnp.logical_and(p_idx < max_pages, page_live(p_idx)))
            def _wait(gg=gg, p_idx=p_idx):
                page = tables_ref[lane * max_pages + p_idx]
                pltpu.make_async_copy(
                    kvpool_ref.at[page],
                    kv_buf.at[slot, :, pl.ds(gg * page_size, page_size)],
                    sem.at[slot, gg]).wait()

    def block_live(j):
        return page_live(j * g_pages)  # first page live <=> any page live

    # deep prefetch pipeline (N-stage slot rotation): the prologue launches
    # the first nbuf-1 live blocks; iteration j then waits its slot and
    # refills the PREVIOUS iteration's slot ((j-1) % nbuf, provably
    # consumed — its loads fed the loop-carried accumulator) with block
    # j+nbuf-1.  Refilling the CURRENT slot (block j+nbuf) would start a
    # DMA into the very buffer this iteration is about to read.  Liveness
    # is a pure predicate of the page index (length is constant
    # in-kernel), monotone decreasing, so every started DMA is waited
    # exactly once.
    start_block(0, 0)  # block 0's first page is always live (length >= 0)
    for jj in range(1, nbuf - 1):
        if jj < n_blocks:
            @pl.when(block_live(jj))
            def _prologue(jj=jj):
                start_block(jj, jj)

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, nbuf)

        def attend(mla):
            m, l, acc = mla
            wait_block(j, slot)

            @pl.when(jnp.logical_and(j + nbuf - 1 < n_blocks,
                                     block_live(j + nbuf - 1)))
            def _prefetch():
                start_block(j + nbuf - 1,
                            jax.lax.rem(j + nbuf - 1, nbuf))

            k = kv_buf[slot, 0].astype(jnp.float32)   # (G*S, Hkv*D)
            v = kv_buf[slot, 1].astype(jnp.float32)
            pos = j * gs + jax.lax.broadcasted_iota(
                jnp.int32, (gs, h), 0)
            mask = pos <= length                  # (G*S, H)
            # rows of dead/unfetched pages hold stale VMEM (possibly NaN):
            # the score side is neutralized by the mask's where below, but
            # V rides a 0-weighted SUM (0 * NaN = NaN) — zero it explicitly
            v = jnp.where(pos[:, :1] <= length, v, 0.0)
            if g > 1:
                k = dot2(k, expand)               # (G*S, H*D) GQA broadcast
                v = dot2(v, expand)
            s = dot2(k * q, sel)                  # (G*S, H) per-head scores
            s = jnp.where(mask, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))   # (1, H)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new) * mask.astype(jnp.float32)      # (G*S, H)
            l_new = l * alpha + p.sum(axis=0, keepdims=True)
            p_exp = dot_sel(p, sel_t)             # (G*S, H*D) head-broadcast
            contrib = (p_exp * v).sum(axis=0, keepdims=True)       # (1, H*D)
            acc_new = acc * dot_sel(alpha, sel_t) + contrib
            return m_new, l_new, acc_new

        # blocks fully beyond the lane's length contribute nothing — skip
        return jax.lax.cond(block_live(j), attend, lambda mla: mla,
                            (m, l, acc))

    init = (jnp.full((1, h), _NEG, jnp.float32),
            jnp.zeros((1, h), jnp.float32),
            jnp.zeros((1, hd), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    l_exp = dot_sel(jnp.maximum(l, 1e-30), sel_t)  # (1, H*D)
    o_ref[0] = (acc / l_exp).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "g_pages", "nbuf"))
def _paged_attn(q, kv_pool, tables, lengths, interpret: bool,
                g_pages: int | None = None, nbuf: int | None = None):
    b, h, d = q.shape
    n_pages, page_size, hkv = (kv_pool.shape[0], kv_pool.shape[2],
                               kv_pool.shape[3])
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    max_pages = tables.shape[1]
    # stage pages as (2, S, Hkv*D) fused K/V blocks: contiguous (free)
    # reshape, keeps every in-kernel dot 2D (see module docstring)
    # rank-3 (B, 1, H*D) so the (1, 1, H*D) block's last two dims equal the
    # array dims exactly (the Pallas TPU block tiling rule)
    q2 = q.reshape(b, 1, h * d)
    kvp = kv_pool.reshape(n_pages, 2, page_size, hkv * d)
    auto_g, auto_nbuf = _block_geometry(page_size, max_pages, hkv * d,
                                        jnp.dtype(kv_pool.dtype).itemsize)
    g_pages = g_pages or auto_g
    nbuf = nbuf or auto_nbuf
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables (flat), lengths
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h * d), lambda lane, *_: (lane, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # KV pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, h * d), lambda lane, *_: (lane, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nbuf, 2, g_pages * page_size, hkv * d),
                       kv_pool.dtype),
            pltpu.SemaphoreType.DMA((nbuf, g_pages)),  # one DMA per page
        ],
    )
    # f32 pools pin HIGHEST on the score dot (the default rounds f32 MXU
    # operands to bf16, costing ~3 decimal digits); bf16 pools keep the
    # fast default — the score operands carry no extra bits to preserve
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.dtype(kv_pool.dtype).itemsize >= 4
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(
        _paged_attn_kernel, page_size=page_size, max_pages=max_pages,
        n_heads=h, head_dim=d, n_kv_heads=hkv,
        sm_scale=1.0 / np.sqrt(d), precision=precision,
        g_pages=g_pages, nbuf=nbuf)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.reshape(-1), lengths, q2, kvp)
    return out.reshape(b, h, d)


def paged_decode_attention(q, kv_pool, tables, lengths,
                           interpret: bool | None = None,
                           g_pages: int | None = None,
                           nbuf: int | None = None):
    """Ragged paged decode attention (MHA or grouped-query).

    q (B, Hq, D) — one query token per lane;
    kv_pool (P, 2, S, Hkv, D) — one layer's page pool in the FUSED layout:
    index 0/1 of axis 1 holds the page's K/V rows adjacently in HBM, so
    the kernel fetches both with one DMA per page (``Hkv < Hq`` selects
    GQA: pages DMA in the compact Hkv form and broadcast to the query
    heads inside the kernel, so KV bandwidth shrinks by Hq/Hkv);
    tables (B, MP) int32 page ids (padded rows point at the scratch page 0);
    lengths (B,) int32 — the current position per lane (inclusive visibility).
    ``g_pages``/``nbuf`` override the auto block geometry (tests pin the
    multi-block pipeline regime; production leaves them None).
    Returns (B, Hq, D).
    """
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _paged_attn(q, kv_pool, tables.astype(jnp.int32),
                       lengths.astype(jnp.int32), interpret,
                       g_pages=g_pages, nbuf=nbuf)
