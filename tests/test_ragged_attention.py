"""Ragged paged-attention kernel family + unified dispatch plan.

Two layers of drift guard (ROADMAP item 2, docs/PERFORMANCE.md "Ragged
paged attention"):

1. Kernel grid — :func:`tpulab.ops.ragged_attention.ragged_paged_attention`
   against a dense per-lane reference, parametrized over dtype
   (f32/bf16) x page size x raggedness shape (all-decode, all-prefill,
   mixed, K+1 verify, page-boundary crossings) x mesh {None,
   {"model": 2}} on the 8-fake-CPU-device harness (pallas interpret
   mode: tier-1 exercises the real kernel path).

2. Engine parity — ContinuousBatcher token streams through the kernels
   (mesh on and off) bit-identical to the XLA gather's for greedy /
   device-sampled / logprobs / host-sampled / speculative requests, the
   gather's greedy streams to the plain forward over each whole sequence,
   with the mixed prefill+decode round running as ONE fused dispatch
   (host-sync count guard, the PR 8 discipline).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine.kv_pool import kv_rows_view
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.engine.paged_steps import (_gather_attend,
                                       _gather_attend_latent)
from tpulab.models.transformer import (early_exit_draft,
                                       init_transformer_params)
from tpulab.ops import sparse_attention as sa
from tpulab.ops import ragged_attention as ra
from tpulab.ops.ragged_attention import (ragged_latent_attention,
                                         ragged_paged_attention)
from tpulab.parallel import make_mesh

from helpers_engine import TokenGate, greedy_reference
from helpers_attention import (BF16_ATOL, BF16_RTOL, assert_operand_rule,
                               assert_parents_bits, kernel_eqns, pallas_calls,
                               sparse_attend_case, sparse_decode_case)

# ------------------------------------------------------------ kernel ----


def _reference(q, k_pool, v_pool, tables, q_lens, kv_lens):
    """Dense per-lane reference (f32 numpy): query j of lane b sits at
    position kv_lens[b] - q_lens[b] + j and attends positions <= it."""
    b, m, h, d = q.shape
    hkv = k_pool.shape[2]
    g = h // hkv
    out = np.zeros(q.shape, np.float32)
    for bb in range(b):
        k_ctx = np.asarray(k_pool[tables[bb]], np.float32).reshape(-1, hkv, d)
        v_ctx = np.asarray(v_pool[tables[bb]], np.float32).reshape(-1, hkv, d)
        for j in range(int(q_lens[bb])):
            pos = int(kv_lens[bb]) - int(q_lens[bb]) + j
            for hh in range(h):
                hk = hh // g
                s = (np.asarray(q[bb, j, hh], np.float32)
                     @ k_ctx[:pos + 1, hk].T) / np.sqrt(d)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[bb, j, hh] = p @ v_ctx[:pos + 1, hk]
    return out


def _pool(k_pool, v_pool, dtype=None):
    """One layer's K and V pages ``(P, S, Hkv, D)`` as a page store of
    one layer in the engine's layout, ``(1, P, 2, S, Hkv*D)``."""
    kv = kv_rows_view(jnp.stack([k_pool, v_pool], axis=1))[None]
    return kv.astype(dtype) if dtype is not None else kv


def _shape_case(name, page_size):
    """(q_lens, kv_lens, M) per raggedness shape, 4 lanes (one inactive
    for all but the all-* shapes).  M is the SAME (2*page_size) for
    every shape on purpose: raggedness lives in q_lens/kv_lens (the
    padded query rows are masked), so the whole grid shares one
    compiled kernel per (dtype, page size, mesh) — the grid stays
    affordable inside the tier-1 budget."""
    s = page_size
    m = 2 * s
    return {
        # one query per live lane, lengths straddling page boundaries
        "all_decode": ([1, 1, 1, 0], [2 * s + 1, s, 3, 0], m),
        # fresh prompts: kv_lens == q_lens (no prior context)
        "all_prefill": ([s + 3, 2 * s, 5, 3], [s + 3, 2 * s, 5, 3], m),
        # decode + chunk + verify + idle in one batch
        "mixed": ([1, s + 2, 5, 0], [2 * s, 2 * s + 2, s + 5, 0], m),
        # K+1 verify (k=4) at varied context depths
        "verify": ([5, 5, 5, 5], [7, s + 5, 2 * s + 5, 3 * s], m),
        # segments crossing page boundaries exactly at/around the edge
        "page_cross": ([4, 4, 1, 1], [s + 2, 2 * s, s + 1, s], m),
        # q_lens of 0, 1 and a chunk: a lane without rows is skipped, also
        # one that has context in its pages (the chunk call of a round)
        "skipped": ([0, 1, s + 3, 0], [2 * s, 2 * s + 1, 2 * s + 3, 0], m),
    }[name]


@functools.lru_cache(maxsize=None)
def _grid_kernel(mesh_n, g_pages=None, nbuf=None):
    """The kernel as the grid calls it, jitted once a mesh: the shapes of a
    (dtype, page size) share one compiled program (an eager ``shard_map``
    is traced and compiled again on every call)."""
    mesh = (make_mesh({"model": mesh_n}, jax.devices()[:mesh_n])
            if mesh_n else None)
    return jax.jit(functools.partial(ragged_paged_attention, mesh=mesh,
                                     g_pages=g_pages, nbuf=nbuf))


#: ``(group size g, KV heads, head width)`` of the one-row cases: the three
#: cells' (Mistral 4 x 8 x 128, Qwen3-Next 8 x 2 x 256, Jamba 20 x 1 x 128),
#: plain MHA, and each other value once more
_ONE_ROW_HEADS = [(1, 2, 128), (1, 8, 256), (4, 8, 128), (8, 2, 256),
                  (20, 1, 128), (20, 2, 256)]
_GRID = [(shape, dtype, page_size, mesh_n, (2, 2, 16))
         for mesh_n in (None, 2) for page_size in (4, 8)
         for dtype in ("float32", "bfloat16")
         for shape in ("all_decode", "all_prefill", "mixed", "verify",
                       "page_cross", "skipped")]
_GRID += [("one_row", dtype, page_size, mesh_n, heads)
          for heads in _ONE_ROW_HEADS for mesh_n in (None, 2)
          for page_size in (8, 16) for dtype in ("float32", "bfloat16")
          if mesh_n is None or heads[1] % mesh_n == 0]
#: the latent kernel at more than one row a lane: ``(rows a lane, heads,
#: heads a tile where the case pins them)``: every head of the lane one
#: tile, five heads a tile and four tiles a lane, and the agent cell's
#: chunk call (eight heads a tile, eight tiles a lane)
_GRID += [("latent", dtype, 8, None, rows)
          for rows in ((16, 4, None), (64, 20, 5), (512, 64, 8))
          for dtype in ("float32", "bfloat16")]


def _one_row_case(page_size):
    """``(q_lens, kv_lens, M, table width, g_pages, nbuf)`` of the one-row
    shape: blocks of two pages in a pipeline two deep.  Lane 0's context is
    four blocks (more than ``nbuf``: the loop refills a slot); lane 1 ends
    on the first key of its second block's second page; lane 2 holds no row
    (no DMA, its rows unwritten); lane 3's second block has one live page,
    so the block's other half is what lane 1 left in the slot; lane 4 ends
    on a block's last key.  :func:`_poisoned` makes every key past a lane's
    length no number, so the stale half and the tail of each last page
    reach the value product only through ``_zero_rows_past``."""
    s = page_size
    return ([1, 1, 0, 1, 1], [6 * s + 3, 3 * s + 1, 2 * s, 2 * s + 3, 4 * s],
            1, 8, 2, 2)


def _latent_case(m):
    """``(q_lens, kv_lens, table width)`` of the latent rows cases, at
    blocks of ``gs`` = 256 keys (32 pages of 8).  Lane 0's chunk starts at
    position 0: no row sees a whole block.  Lane 1's starts three keys
    into its second block.  Lane 2 holds fewer rows than ``m`` and its
    first row sits on a block's last key.  Lane 3 holds no row, lane 5
    one, on its only block's last key.  Lane 4's last block holds one live
    page.  Keys past a lane's length are no number (:func:`_poisoned`)."""
    gs = 256
    q_lens = [m, m, m - 3, 0, m, 1]
    kv_lens = [m, m + gs + 3, m - 3 + 2 * gs - 1, 300,
               -(-m // gs) * gs + gs + 5, gs]
    return q_lens, kv_lens, -(-max(kv_lens) // 8) + 2


def _latent_grid_case(dt, m, h, heads_tile):
    """A latent rows case of the grid against the XLA gather: the rows a
    lane holds agree, the rows past them are zero, a lane without rows is
    unwritten."""
    q_lens, kv_lens, mp = _latent_case(m)
    b, ps, w, row, v_width, scale = len(q_lens), 8, 48, 64, 32, 0.2
    ks = jax.random.split(jax.random.PRNGKey(m), 2)
    q = jax.random.normal(ks[0], (b, m, h, w), jnp.float32).astype(dt)
    pool = jax.random.normal(ks[1], (2, b * mp + 1, 1, ps, row),
                             jnp.float32).astype(dt)
    pool = pool.at[..., w:].set(0.0)             # the row's zero padding
    tables = jnp.asarray(np.arange(1, b * mp + 1).reshape(b, mp), jnp.int32)
    plan = ra._latent_plan(m, h, row, v_width, ps, mp, dt, dt, heads_tile)
    assert (plan.g_pages * ps, plan.heads_tile) == (256, heads_tile or h)
    assert plan.rows == plan.heads_tile * m
    got = np.asarray(ra._latent_attn(
        q, _poisoned(pool, tables, q_lens, kv_lens),
        jnp.ones((1,), jnp.int32), tables, jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(kv_lens, jnp.int32), v_width=v_width, sm_scale=scale,
        interpret=True, heads_tile=heads_tile), np.float32)
    # the reference on a few heads (it holds a score for every key of the
    # table): the first and last of a tile, of the lane
    heads = sorted({0, h // 2 - 1, h // 2, h - 1} | (
        {heads_tile - 1, heads_tile} if heads_tile else set()))
    pos = jnp.asarray(kv_lens) - jnp.asarray(q_lens)
    want = np.asarray(_gather_attend_latent(
        q[:, :, heads], pool[1, :, 0], tables,
        pos[:, None] + jnp.arange(m)[None, :], v_width, scale, dt),
        np.float32)
    tol = (dict(rtol=2e-5, atol=2e-5) if dt == jnp.float32
           else dict(rtol=BF16_RTOL, atol=BF16_ATOL))
    for bb, n in enumerate(q_lens):
        if not n:
            assert np.isnan(got[bb]).all()
            continue
        np.testing.assert_allclose(got[bb, :n][:, heads], want[bb, :n],
                                   **tol)
        assert not got[bb, n:].any()


def _poisoned(pool, tables, q_lens, kv_lens):
    """``pool`` with NaN in every row of the lanes' pages that no query
    sees: the keys past a lane's length (the tail of its last page and the
    pages after it) and every page of a lane without a row."""
    page_size = pool.shape[3]
    for lane, (qn, kvn) in enumerate(zip(q_lens, kv_lens)):
        live = kvn if qn else 0
        for i, page in enumerate(np.asarray(tables[lane])):
            first = max(live - i * page_size, 0)
            if first < page_size:
                pool = pool.at[:, int(page), :, first:].set(jnp.nan)
    return pool


@pytest.mark.parametrize("shape,dtype,page_size,mesh_n,heads", _GRID, ids=[
    "-".join(map(str, (c[0], c[1], c[2], c[3], "x".join(map(str, c[4])))))
    for c in _GRID])
def test_kernel_matches_reference_grid(shape, dtype, page_size, mesh_n,
                                       heads):
    """The parity drift guard of the satellite grid: every raggedness
    shape x dtype x page size x mesh agrees with the dense reference; at
    one row a lane (``one_row``: the stacked kernel) also every group size
    x KV heads x head width the serving cells have; the latent kernel at
    more than one row a lane (``latent``) against its own XLA form."""
    dt = jnp.dtype(dtype)
    if shape == "latent":
        return _latent_grid_case(dt, *heads)
    rng = jax.random.PRNGKey(hash((shape, page_size)) % 2**31)
    g, hkv, d = heads
    hq = g * hkv
    if shape == "one_row":
        q_lens, kv_lens, m, mp, g_pages, nbuf = _one_row_case(page_size)
    else:
        q_lens, kv_lens, m = _shape_case(shape, page_size)
        mp = 4   # fixed table width: every shape reuses one compiled kernel
        g_pages = nbuf = None
    b = len(q_lens)
    pages = b * mp + 1
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, m, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (pages, page_size, hkv, d),
                               jnp.float32)
    v_pool = jax.random.normal(ks[2], (pages, page_size, hkv, d),
                               jnp.float32)
    tables = jnp.asarray(
        np.arange(1, b * mp + 1).reshape(b, mp), jnp.int32)
    pool = _pool(k_pool, v_pool, dt)
    got = _grid_kernel(mesh_n, g_pages, nbuf)(
        q.astype(dt),
        _poisoned(pool, tables, q_lens, kv_lens) if shape == "one_row"
        else pool, 0, tables, jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(kv_lens, jnp.int32))
    if dt == jnp.float32:
        want = _reference(np.asarray(q), np.asarray(k_pool),
                          np.asarray(v_pool), np.asarray(tables),
                          q_lens, kv_lens)
        tol = dict(rtol=2e-5, atol=2e-5)
    else:
        # a bf16 store against the XLA form of the same step on the same
        # bf16 pages: both round the probabilities and the output to bf16
        pos = (jnp.asarray(kv_lens) - jnp.asarray(q_lens))[:, None] \
            + jnp.arange(m)[None, :]
        want = np.asarray(_gather_attend(
            q.astype(dt), pool[0, :, 0], pool[0, :, 1], tables, pos, dt),
            np.float32).reshape(b, m, hq, d)
        tol = dict(rtol=BF16_RTOL, atol=BF16_ATOL)
    got = np.asarray(got, np.float32)
    for bb in range(b):
        n = int(q_lens[bb])
        np.testing.assert_allclose(got[bb, :n], want[bb, :n], **tol)
        if shape == "one_row" and not n:
            # unwritten: the interpreter starts an output as NaN, and a
            # lane computed with no valid row would write numbers
            assert np.isnan(got[bb]).all()


# -- a block of adjacent pages is one copy: the table decides, bits do not --

_RUN_PS, _RUN_MP, _RUN_ROW = 8, 80, 128
_RUN_KERNELS = {          # name -> (pages a block, pinned or the kernel's own)
    "ragged_paged_decode": 8, "ragged_paged_attention": 8,
    "ragged_latent_attention-one-row": 32, "ragged_latent_attention-rows": 32,
    "sparse_paged_decode": 32, "sparse_paged_attention": 32}
_RUN_TABLES = ["ascending", "descending", "shuffled", "run_from_mid_block",
               "runs_and_scattered_mixed", "partial_last_block",
               "lanes_without_rows", "shared_pages"]


def _run_tables(name, g, live_pages):
    """``(B, MP)`` page ids of the layout ``name``: lane ``l`` owns ids ``1
    + l * MP`` on; ``g`` pages a key block."""
    b, mp = len(live_pages), _RUN_MP
    own = 1 + np.arange(b * mp).reshape(b, mp)
    rng = np.random.default_rng(7)
    if name == "descending":
        return own[:, ::-1].copy()
    if name == "shuffled":
        return np.stack([rng.permutation(row) for row in own])
    if name == "run_from_mid_block":
        # seven scattered ids, then one run: block 0 is none, block 1 is
        return np.concatenate([own[:, :-8:-1], own[:, :mp - 7]], axis=1)
    if name == "runs_and_scattered_mixed":
        out = own.copy()
        for j in range(1, -(-mp // g), 2):          # odd blocks scattered
            out[:, j * g:(j + 1) * g] = rng.permuted(
                out[:, j * g:(j + 1) * g], axis=1)
        return out
    if name == "partial_last_block":
        # the engine's table: zeros past the pages a lane holds
        return np.where(np.arange(mp)[None, :]
                        < np.asarray(live_pages)[:, None], own, 0)
    return own            # ascending, lanes_without_rows, shared_pages


def _run_case(kernel, table_name, permuted=False):
    """The kernel's call on one set of LOGICAL rows a lane under the layout
    ``table_name``: ``(got, want, rows a lane holds)``.  ``permuted``: the
    same table with every id sent through one permutation of all the ids,
    so that no block of it is a run.  Lane 0 ends inside
    its third block, lane 1 on its first block's last key, lane 2 holds no
    row, lane 3 ends inside its first block, lane 4 one key into its third,
    lane 5 mid-way through its second.  Keys past a lane's length are no
    number (:func:`_poisoned`)."""
    ps, mp, row = _RUN_PS, _RUN_MP, _RUN_ROW
    g = _RUN_KERNELS[kernel]
    gs = g * ps
    kv_lens = [2 * gs + 5 * ps + 3, gs, 2 * gs, gs // 2 + 3, 2 * gs + 1,
               gs + gs // 2]
    m = 1 if kernel.endswith(("decode", "one-row")) else 3
    q_lens = [m, m, 0, m, m - (m > 1), m]
    if table_name == "lanes_without_rows":
        q_lens = [0, m, 0, 0, m, 0]
    b = len(kv_lens)
    live_pages = [(n - 1) // ps + 1 for n in kv_lens]
    tables = _run_tables(table_name, g, live_pages)
    latent = "latent" in kernel
    parts = 1 if latent else 2
    rng = np.random.default_rng(11)
    logical = rng.standard_normal((2, b, mp, parts, ps, row)).astype(
        np.float32)
    if table_name == "shared_pages":
        # lane 1's first block IS lane 0's (a shared prompt prefix)
        logical[:, 1, :g] = logical[:, 0, :g]
        tables[1, :g] = tables[0, :g]
    if permuted:
        tables = np.concatenate([[0], 1 + np.random.default_rng(
            3).permutation(b * mp)])[tables]
    pool = np.zeros((2, 1 + b * mp, parts, ps, row), np.float32)
    for lane in range(b):
        for i in range(live_pages[lane]):
            pool[:, tables[lane, i]] = logical[:, lane, i]
    pool, tables = jnp.asarray(pool), jnp.asarray(tables, jnp.int32)
    dirty = _poisoned(pool, tables, q_lens, kv_lens)
    ql, kl = jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32)
    layer = jnp.ones((1,), jnp.int32)
    pos = (kl - ql)[:, None] + jnp.arange(m)[None, :]
    if kernel.startswith("ragged_paged"):
        h, hkv, d = 4, 2, 64
        q = jnp.asarray(rng.standard_normal((b, m, h, d)), jnp.float32)
        got = ra._ragged_attn(q, dirty, layer, tables, ql, kl,
                              interpret=True, g_pages=g, nbuf=2)
        kv = np.asarray(pool[1]).reshape(-1, 2, ps, hkv, d)
        want = _reference(np.asarray(q), kv[:, 0], kv[:, 1],
                          np.asarray(tables), q_lens, kv_lens)
    elif latent:
        h, w, v_width = 4, 96, 64
        q = jnp.asarray(rng.standard_normal((b, m, h, w)), jnp.float32)
        got = ra._latent_attn(q, dirty, layer, tables, ql, kl,
                              v_width=v_width, sm_scale=0.2, interpret=True)
        want = _gather_attend_latent(q, pool[1, :, 0], tables, pos, v_width,
                                     0.2, jnp.float32)
    else:
        h, hkv, d = 4, 2, 64
        rows = [(lane, j) for lane in range(b) for j in range(q_lens[lane])]
        q = jnp.asarray(rng.standard_normal((len(rows), h, d)), jnp.float32)
        row_lane = jnp.asarray([lane for lane, _ in rows], jnp.int32)
        sees = np.asarray([kv_lens[lane] - q_lens[lane] + j
                           for lane, j in rows])
        mask = jnp.asarray((rng.random((len(rows), mp * ps)) < 0.5)
                           & (np.arange(mp * ps)[None, :] <= sees[:, None]))
        live = (ql > 0).astype(jnp.int32)
        if kernel == "sparse_paged_decode":
            at = np.cumsum([0] + q_lens[:-1])       # lane -> its one row
            got = sa._sparse_decode(
                jnp.where((ql > 0)[:, None, None], q[at % len(rows)], 0.0),
                jnp.where((ql > 0)[:, None], mask[at % len(rows)], False),
                dirty, layer, tables, live, kl, interpret=True)
            got = got[np.asarray([lane for lane, _ in rows])]
        else:
            got = sa._sparse_attn(q, mask, row_lane, dirty, layer, tables,
                                  live, kl, interpret=True)
        want = sa.sparse_attend_xla(q, mask, row_lane, pool[1], tables,
                                    jnp.float32)
        return np.asarray(got), np.asarray(want), None
    return np.asarray(got), np.asarray(want), q_lens


@pytest.mark.parametrize("table_name", _RUN_TABLES)
@pytest.mark.parametrize("kernel", sorted(_RUN_KERNELS))
def test_a_run_of_pages_is_one_copy_and_the_same_bits(kernel, table_name):
    """Every kernel of the family on every shape of table: blocks that are
    ascending runs (one copy), scattered blocks (a copy a page), a run that
    starts mid-block, both kinds in one lane, a partial last block, lanes
    without rows, pages shared between lanes.  Each agrees with the XLA
    form within the grid's limits AND, bit for bit, with the same logical
    rows under the same table sent through a permutation of all the ids:
    what a block's copies land is the same bytes in the same place,
    whichever path."""
    got, want, q_lens = _run_case(kernel, table_name)
    base, _want, _q_lens = _run_case(kernel, table_name, permuted=True)
    tol = dict(rtol=2e-5, atol=2e-5)
    if q_lens is None:                      # the sparse kernels: rows
        np.testing.assert_allclose(got, want, **tol)
    else:
        assert any(q_lens)
        for lane, n in enumerate(q_lens):
            np.testing.assert_allclose(got[lane, :n], want[lane, :n], **tol)
    np.testing.assert_array_equal(got, base)


def test_kernel_long_walk_exceeds_pipeline_depth():
    """More KV blocks than nbuf slots exercises the in-loop slot refill
    (the DMA pipeline inherited from the single-query kernel)."""
    rng = jax.random.PRNGKey(3)
    hq, d, ps, mp = 2, 16, 4, 12
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 3, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (mp + 1, ps, hq, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (mp + 1, ps, hq, d), jnp.float32)
    tables = jnp.asarray(np.arange(1, mp + 1)[None], jnp.int32)
    q_lens = jnp.asarray([3], jnp.int32)
    kv_lens = jnp.asarray([ps * mp - 1], jnp.int32)
    got = ragged_paged_attention(
        q, _pool(k_pool, v_pool), 0, tables, q_lens, kv_lens,
        g_pages=1, nbuf=2)  # pin the multi-block pipeline regime
    want = _reference(np.asarray(q), np.asarray(k_pool),
                      np.asarray(v_pool), np.asarray(tables), [3],
                      [ps * mp - 1])
    np.testing.assert_allclose(np.asarray(got)[0], want[0],
                               rtol=2e-5, atol=2e-5)


def test_kernel_rejects_unsplittable_heads_under_mesh():
    mesh = make_mesh({"model": 2}, jax.devices()[:2])
    q = jnp.zeros((1, 1, 3, 16), jnp.float32)
    kvp = jnp.zeros((1, 2, 2, 4, 3 * 16), jnp.float32)
    with pytest.raises(ValueError, match="divide the mesh"):
        ragged_paged_attention(q, kvp, 0, jnp.zeros((1, 1), jnp.int32),
                               jnp.ones((1,), jnp.int32),
                               jnp.ones((1,), jnp.int32), mesh=mesh)


@pytest.mark.parametrize("case", ["decode", "mixed", "mesh4"])
def test_kernel_walks_the_layer_it_is_given(case):
    """The kernel on the WHOLE pool at a layer index > 0 of a three-layer
    pool whose layers hold different contents, against the gather path on
    that layer: a wrong layer offset in the page DMAs reads another
    layer's pages.  Decode (q_lens 1), a mixed round, and the sharded
    walk on four virtual devices."""
    ps, mp, d = 8, 3, 16
    hq, hkv = (8, 4) if case == "mesh4" else (4, 2)
    q_lens, kv_lens, m = {
        "decode": ([1, 1, 1, 0], [2 * ps + 1, ps, 3, 0], 1),
        "mixed": ([1, ps + 2, 5, 0], [2 * ps, 2 * ps + 2, ps + 5, 0],
                  2 * ps),
        "mesh4": ([1, ps + 2, 5, 1], [2 * ps, 2 * ps + 2, ps + 5, 3],
                  2 * ps),
    }[case]
    b = len(q_lens)
    pages = b * mp + 1
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    q = jax.random.normal(ks[0], (b, m, hq, d), jnp.float32)
    # every layer its own values, so no two layers can be mistaken
    pool = jax.random.normal(ks[1], (3, pages, 2, ps, hkv * d), jnp.float32)
    tables = jnp.asarray(np.arange(1, b * mp + 1).reshape(b, mp), jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    pos = (kv_lens - q_lens)[:, None] + jnp.arange(m)[None, :]
    mesh = (make_mesh({"model": 4}, jax.devices()[:4])
            if case == "mesh4" else None)
    valid = np.arange(m)[None, :] < np.asarray(q_lens)[:, None]
    outs = {}
    for layer in (1, 2):
        got = np.asarray(ragged_paged_attention(
            q, pool, layer, tables, q_lens, kv_lens, mesh=mesh))
        want = np.asarray(_gather_attend(
            q, pool[layer, :, 0], pool[layer, :, 1], tables, pos,
            jnp.float32)).reshape(b, m, hq, d)
        np.testing.assert_allclose(got[valid], want[valid],
                                   rtol=2e-5, atol=2e-5)
        outs[layer] = got[valid]
    assert np.abs(outs[1] - outs[2]).max() > 1e-2   # the layers do differ


def _round_case(kernel, dtype=jnp.float32):
    """A mixed round's attention on either kernel: ``(attend, reference,
    q, q_lens, kv_lens)``.  Lanes 1 and 4 hold a chunk, 2 and 5 decode, 0
    and 3 hold nothing; lane 0's pages and query rows are NaN, lane 3 has
    sound context in its pages.  ``attend(q, q_lens)`` is the kernel at
    ``q``'s width, ``reference`` the XLA gather at the same; queries and
    page store in ``dtype``."""
    ps, mp, m, h = 8, 3, 2 * 8, 4
    q_lens = np.asarray([0, ps + 3, 1, 0, 5, 1], np.int32)
    kv_lens = np.asarray([ps, 2 * ps + 3, ps + 1, 2 * ps, 5, 3 * ps],
                         np.int32)
    b = len(q_lens)
    tables = jnp.asarray(np.arange(1, b * mp + 1).reshape(b, mp), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(17), 2)
    width = 48 if kernel == "latent" else 16
    q = jax.random.normal(ks[0], (b, m, h, width), jnp.float32).astype(dtype)
    q = q.at[0].set(jnp.nan)
    if kernel == "latent":
        pool = jax.random.normal(ks[1], (2, b * mp + 1, 1, ps, 64),
                                 jnp.float32).astype(dtype)
        pool = pool.at[..., width:].set(0.0)     # the row's zero padding
        kw = dict(v_width=32, sm_scale=0.2)

        def attend(q, q_lens):
            return ragged_latent_attention(q, pool, 1, tables, q_lens,
                                           jnp.asarray(kv_lens), **kw)

        def reference(q, qpos):
            return _gather_attend_latent(q, pool[1, :, 0], tables, qpos,
                                         kw["v_width"], kw["sm_scale"],
                                         dtype)
    else:
        pool = jax.random.normal(ks[1], (2, b * mp + 1, 2, ps, 2 * width),
                                 jnp.float32).astype(dtype)
        mesh = (make_mesh({"model": 2}, jax.devices()[:2])
                if kernel == "kv-mesh2" else None)

        def attend(q, q_lens):
            return ragged_paged_attention(q, pool, 1, tables, q_lens,
                                          jnp.asarray(kv_lens), mesh=mesh)

        def reference(q, qpos):
            return _gather_attend(q, pool[1, :, 0], pool[1, :, 1], tables,
                                  qpos, dtype).reshape(q.shape)
    pool = pool.at[:, 1:1 + mp].set(jnp.nan)     # lane 0's pages
    return attend, reference, q, q_lens, kv_lens


@pytest.mark.parametrize("kernel", ["kv", "kv-mesh2", "latent"])
def test_a_lane_pays_for_the_query_rows_it_holds(kernel):
    """One call over a round's lanes, then the round in two calls (the
    chunk lanes at M, the decode lanes at 1, ``q_lens`` zeroed for the
    other kind): a lane without rows is skipped, NaN pages and all, and
    the two calls give the one call's rows."""
    attend, reference, q, q_lens, kv_lens = _round_case(kernel)
    b, m = q.shape[:2]
    start = kv_lens - q_lens
    one = np.asarray(attend(q, jnp.asarray(q_lens)))
    want = np.asarray(reference(
        q, jnp.asarray(start[:, None] + np.arange(m)[None, :])))
    valid = np.arange(m)[None, :] < q_lens[:, None]
    assert np.isfinite(want[valid]).all()
    np.testing.assert_allclose(one[valid], want[valid], rtol=2e-5,
                               atol=2e-5)
    # a skipped lane's block is unwritten (the interpreter starts an output
    # as NaN; a lane computed with no valid row would write zeros)
    assert np.isnan(one[q_lens == 0]).all()

    chunk_lens = np.where(q_lens > 1, q_lens, 0)
    dec_lens = (q_lens == 1).astype(np.int32)
    chunks = np.asarray(attend(q, jnp.asarray(chunk_lens)))
    decodes = np.asarray(attend(q[:, :1], jnp.asarray(dec_lens)))
    assert chunks.shape == one.shape and decodes.shape[:2] == (b, 1)
    in_chunk = valid & (chunk_lens > 0)[:, None]
    np.testing.assert_allclose(chunks[in_chunk], one[in_chunk], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(decodes[dec_lens > 0, 0],
                               one[dec_lens > 0, 0], rtol=2e-5, atol=2e-5)
    assert np.isnan(chunks[chunk_lens == 0]).all()
    assert np.isnan(decodes[dec_lens == 0]).all()


@pytest.mark.parametrize("kernel", ["kv", "latent"])
def test_a_skipped_lane_starts_no_dma_and_writes_nothing(kernel):
    """Everything a grid step does is under ``q_lens[lane] > 0``: outside
    that one conditional the kernel body holds no DMA start or wait and no
    store, only the reads of its scalar words."""
    attend, _reference, q, q_lens, _kv_lens = _round_case(kernel)
    (call,) = pallas_calls(attend, q, jnp.asarray(q_lens))
    body = call.params["jaxpr"]
    top = [eqn.primitive.name for eqn in body.eqns]
    assert top.count("cond") == 1
    assert not {"dma_start", "dma_wait", "swap", "addupdate"} & set(top)
    inside = str(body.eqns[top.index("cond")].params["branches"])
    assert "dma_start" in inside and "dma_wait" in inside


@pytest.mark.parametrize("rows", [1, 16])
def test_the_latent_kernels_tile_follows_the_rows_a_lane(rows):
    """One row a lane keeps the program it had: every head of the lane one
    tile, the statistics a loop's carry (two scratch operands: the page
    pipeline and its semaphores).  More rows take the rows kernel: the
    plan's tile, the statistics in three more scratch buffers."""
    attend, _reference, q, q_lens, _kv_lens = _round_case("latent")
    b, _m, h, _w = q.shape
    (call,) = pallas_calls(attend, q[:, :rows], jnp.asarray(q_lens))
    mapping = call.params["grid_mapping"]
    plan = ra._latent_plan(rows, h, 64, 32, 8, 3, q.dtype, q.dtype)
    assert plan.heads_tile == h and plan.rows == max(rows * h, 16)
    assert mapping.grid == (b, h // plan.heads_tile)
    assert mapping.num_scratch_operands == (2 if rows == 1 else 5)
    # the accumulator is the carry of a loop at one row alone
    carried = {v.aval.shape for e in kernel_eqns(
        attend, q[:, :rows], jnp.asarray(q_lens))
        if e.primitive.name == "while" for v in e.outvars}
    assert ((plan.rows, 32) in carried) == (rows == 1)


def _rule_case(kernel, dtype):
    """``(call, arguments, shape of the staged block)`` of one of the five
    walks at a geometry this suite compiles anyway."""
    if kernel.startswith("sparse"):
        if kernel == "sparse_paged_attention":
            args = sparse_attend_case(dtype)
            return (lambda *a: sa.sparse_attend(*a, dtype, use_kernel=True),
                    args[:-1], (5 * 8, 64))
        return sa.sparse_attend_decode, sparse_decode_case(dtype)[0], (40, 64)
    attend, _ref, q, q_lens, _kv = _round_case(
        "latent" if kernel == "ragged_latent_attention" else "kv", dtype)
    if kernel == "ragged_paged_attention-one-row":
        q, q_lens = q[:, :1], (q_lens == 1).astype(np.int32)
    return attend, (q, jnp.asarray(q_lens)), (24, q.shape[-1] * 2
                                              if "latent" not in kernel
                                              else 64)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", [
    "ragged_paged_attention", "ragged_paged_attention-one-row",
    "ragged_latent_attention", "sparse_paged_attention",
    "sparse_paged_decode"])
def test_both_products_take_their_operands_from_the_stores_dtype(kernel,
                                                                 dtype):
    """The one rule of the five walks, read off the kernel body's jaxpr: a
    bf16 store gives both products bf16 operands in one default pass with
    float32 accumulation and no float32 copy of the staged block; a
    float32 store keeps ``HIGHEST`` on both."""
    fn, args, block = _rule_case(kernel, jnp.dtype(dtype))
    assert_operand_rule(fn, args, jnp.dtype(dtype), block)


@pytest.mark.parametrize("kernel", [
    "ragged_paged_attention", "ragged_paged_attention-one-row",
    "ragged_latent_attention"])
def test_float32_store_gives_the_parents_bits(kernel):
    """A float32 store keeps both products at ``HIGHEST``: the rows of the
    commit before the operand rule, to the bit (a skipped lane's rows are
    unwritten: NaN in the interpreter then and now; the one-row walk's
    entry: see ``assert_parents_bits``)."""
    fn, args, _block = _rule_case(kernel, jnp.float32)
    assert_parents_bits(kernel, fn(*args))


def test_bf16_latent_store_rounds_where_the_xla_form_does():
    """:func:`test_kernel_matches_reference_grid`'s bf16 half for latent
    pages: kernel and XLA gather round the probabilities to bf16 alike."""
    attend, reference, q, q_lens, kv_lens = _round_case("latent",
                                                        jnp.bfloat16)
    m = q.shape[1]
    got = np.asarray(attend(q, jnp.asarray(q_lens)), np.float32)
    want = np.asarray(reference(q, jnp.asarray(
        (kv_lens - q_lens)[:, None] + np.arange(m)[None, :])), np.float32)
    valid = np.arange(m)[None, :] < q_lens[:, None]
    np.testing.assert_allclose(got[valid], want[valid], rtol=BF16_RTOL,
                               atol=BF16_ATOL)


def test_kernel_takes_a_traced_layer():
    """The layer rides the scalar prefetch: one compiled kernel serves
    every layer, also from inside a ``lax.scan`` over layers."""
    ps, mp, d, h = 8, 2, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(ks[0], (2, 1, h, d), jnp.float32)
    pool = jax.random.normal(ks[1], (3, 2 * mp + 1, 2, ps, h * d),
                             jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    q_lens = jnp.ones((2,), jnp.int32)
    kv_lens = jnp.asarray([ps + 3, 5], jnp.int32)

    def walk(_, layer):
        return None, ragged_paged_attention(q, pool, layer, tables, q_lens,
                                            kv_lens)

    _, scanned = jax.jit(lambda: jax.lax.scan(walk, None, jnp.arange(3)))()
    for layer in range(3):
        np.testing.assert_array_equal(
            np.asarray(scanned[layer]),
            np.asarray(ragged_paged_attention(q, pool, layer, tables,
                                              q_lens, kv_lens)))


def test_the_shape_selects_the_kernel_one_row_stacked_wider_rows_not():
    """The step programs as they lower: a decode block's steps and a
    packed round's decode rows go through ``ragged_paged_decode``, a
    round's chunk rows and the K+1 verify form through
    ``ragged_paged_attention``; one call a layer a segment kind, and
    nothing but the rows' width chooses."""
    from helpers_steps import decode_block, mixed_step
    from tpulab.engine.kv_pool import PagedKVPool
    from tpulab.engine.paged_steps import (pack_round, paged_decode_block,
                                           paged_mixed_step,
                                           paged_ragged_forward)

    params = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=64, n_kv_heads=2)
    kv = PagedKVPool(n_pages=10, page_size=8, n_layers=2, n_heads=2,
                     head_dim=16, dtype=jnp.float32).kv
    tables = np.asarray([[1, 2, 3, 0], [4, 5, 6, 0], [7, 8, 0, 0]], np.int32)
    common = dict(n_heads=4, n_kv_heads=2, n_layers=2,
                  compute_dtype=jnp.float32, use_kernel=True)
    names = []

    def _kernel_names(fn, *args):
        return [call.params["name"] for call in pallas_calls(fn, *args)]

    def traced(step):
        def call(*args):
            names.append(_kernel_names(step, *args))
            return step(*args)
        return call

    decode_block(
        traced(functools.partial(paged_decode_block, lanes=3, max_pages=4,
                                 k=2, **common)),
        params, kv, tables, ([12, 6, 0], [3, 9, 0], [True, True, False],
                             [2, 2, 0]), 2)
    toks, row_lane, row_off, q_lens = pack_round(
        3, {0: np.arange(4)}, {1: 5})
    mixed_step(
        traced(functools.partial(paged_mixed_step, lanes=3, max_pages=4,
                                 **common)),
        params, kv, tables, toks, row_lane, row_off, q_lens, [12, 6, 0])
    i32 = lambda x: jnp.asarray(x, jnp.int32)        # noqa: E731
    names.append(_kernel_names(
        lambda p, kv: paged_ragged_forward(        # K + 1 = 5 rows a lane
            p, kv, i32(tables), i32(np.zeros((3, 5))), i32([5, 5, 0]),
            i32([12, 9, 0]), **common), params, kv))
    block, round_, verify = names
    assert block == ["ragged_paged_decode"] * 2          # the scan's body
    assert round_ == ["ragged_paged_attention", "ragged_paged_decode"] * 2
    assert verify == ["ragged_paged_attention"] * 2


# ------------------------------------------------------------ engine ----

_CASES = ((5, 12), (9, 8))  # (prompt_len, steps): both cross a page


@pytest.fixture(scope="module")
def lm():
    p = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64)
    # trained-model emulation (test_speculative_block): the early-exit
    # draft must actually agree with the target sometimes
    for w in ("wo", "w2"):
        p["layer1"][w] = p["layer1"][w] * 0.05
    return p


def _batcher(lm, mesh_n=None, **kw):
    mesh = (make_mesh({"model": mesh_n}, jax.devices()[:mesh_n])
            if mesh_n else None)
    kw.setdefault("lanes", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_block", 4)  # bound per-mode compile variety
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, page_size=8,
                             compute_dtype=jnp.float32, mesh=mesh, **kw)


def _run_cases(cb):
    """Greedy / device-sampled / logprobs / host-sampled streams through
    one batcher — the four sampling verticals of the parity matrix."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, (n,), np.int32) for n, _ in _CASES]
    out = [list(cb.submit(p, s).result(timeout=300))
           for p, (_, s) in zip(prompts, _CASES)]
    out.append(list(cb.submit(
        prompts[0], 8, sampling=SamplingParams(
            temperature=0.8, seed=42, device=True)).result(timeout=300)))
    toks, lps = cb.submit(prompts[1], 6, logprobs=True).result(timeout=300)
    out.append(list(toks))
    out.append(list(cb.submit(
        prompts[1], 6, sampling=SamplingParams(
            temperature=0.9, top_k=5, seed=7)).result(timeout=300)))
    return out, list(lps)


@pytest.fixture(scope="module")
def gather_ref(lm):
    cb = _batcher(lm, use_kernel=False)
    try:
        return _run_cases(cb)
    finally:
        cb.shutdown()


def test_the_gathers_greedy_streams_are_the_plain_forwards(lm, gather_ref):
    """The XLA gather's greedy streams and logprobs (what the kernels are
    held to below) against a reference that shares no code with the
    engine: the plain forward over each whole sequence."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, (n,), np.int32) for n, _ in _CASES]
    want = [greedy_reference(lm, p, s, 2, 2)
            for p, (_, s) in zip(prompts, _CASES)]
    assert gather_ref[0][:2] == [toks for toks, _ in want]
    assert gather_ref[0][3] == want[1][0][:6]
    np.testing.assert_allclose(gather_ref[1], want[1][1][:6],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["kernel", "kernel_mesh"])
def test_engine_token_parity(lm, gather_ref, mode):
    """Bit-exact tokens across greedy/device-sampled/logprobs/host-sampled
    streams, kernel against XLA attention, mesh on and off: the house
    parity style."""
    kw = {"kernel": dict(use_kernel=True),
          "kernel_mesh": dict(use_kernel=True, mesh_n=2)}[mode]
    cb = _batcher(lm, **kw)
    try:
        out, lps = _run_cases(cb)
        assert cb.dispatch_kinds["mixed"] >= 1
    finally:
        cb.shutdown()
    assert out == gather_ref[0]
    np.testing.assert_allclose(lps, gather_ref[1], rtol=1e-5, atol=1e-5)


def _run_spec(cb):
    rng = np.random.default_rng(1)
    a = rng.integers(0, 64, (5,), np.int32)
    b = rng.integers(0, 64, (9,), np.int32)
    out = [list(cb.submit(a, 10).result(timeout=300)),
           list(cb.submit(b, 6, sampling=SamplingParams(
               temperature=0.7, seed=11, device=True)).result(timeout=300))]
    return out


@pytest.fixture(scope="module")
def spec_ref(lm):
    draft = early_exit_draft(lm, 1)
    ref_cb = _batcher(lm, use_kernel=False, draft_params=draft,
                      draft_n_layers=1)
    try:
        want = _run_spec(ref_cb)
        assert ref_cb.spec_dispatches > 0
        return want
    finally:
        ref_cb.shutdown()


@pytest.mark.parametrize("mesh_n", [None, 2])
def test_speculative_verify_parity(lm, spec_ref, mesh_n):
    """The K+1 verify forward through the ragged kernel (the PR 7
    follow-up retired) == the XLA-gather spec path, mesh on and off;
    speculative dispatches actually ran."""
    draft = early_exit_draft(lm, 1)
    want = spec_ref
    cb = _batcher(lm, use_kernel=True, mesh_n=mesh_n, draft_params=draft,
                  draft_n_layers=1)
    try:
        got = _run_spec(cb)
        assert cb.spec_dispatches > 0
        assert cb.dispatch_kinds["verify"] == cb.spec_dispatches
        assert cb.ragged_dispatches > 0
    finally:
        cb.shutdown()
    assert got == want


def test_mixed_round_is_one_fused_dispatch(lm):
    """The acceptance guard: N simultaneous prompt fills fold into ONE
    ragged dispatch (never a program a lane), and a mixed prefill+decode
    round costs one dispatch = one host sync."""
    cb = _batcher(lm, use_kernel=False, lanes=3)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 64, (6,), np.int32) for _ in range(3)]
    try:
        cb.submit(prompts[0], 1).result(timeout=300)  # warm the bucket
        d0 = cb.decode_dispatches
        s0 = cb.decode_host_syncs
        m0 = cb.dispatch_kinds["mixed"]
        with cb._cv:     # simultaneous: one admission pass sees all three
            futs = [cb.submit(p, 1) for p in prompts]
        outs = [list(f.result(timeout=300)) for f in futs]
        assert all(len(o) == 1 for o in outs)
        # every round is one dispatch and one blocking fetch; the three
        # prompt fills fold into at most two rounds, never one program per
        # lane
        assert cb.decode_dispatches - d0 <= 2
        assert cb.decode_host_syncs - s0 == cb.decode_dispatches - d0
        assert cb.dispatch_kinds["mixed"] - m0 == cb.decode_dispatches - d0

        # mixed prefill+decode: a prompt arriving mid-decode rides the
        # same fused round as the decoding lane
        held = TokenGate(3)
        f0 = cb.submit(prompts[0], 16, on_token=held)
        assert held.wait(60)
        d1 = cb.decode_dispatches
        f1 = cb.submit(prompts[1], 4)
        held.release()
        r1 = f1.result(timeout=300)
        r0 = f0.result(timeout=300)
        assert cb.dispatch_kinds["mixed"] - m0 >= 3
        assert cb.decode_host_syncs == cb.decode_dispatches
        assert len(r0) == 16 and len(r1) == 4
    finally:
        cb.shutdown()


def test_chunked_prefill_prefix_cache_and_resume(lm):
    """Multi-round chunked prefill (prefill_chunk bounds the per-round
    segment), prefix-cache hits, and preempt/resume all compose: token
    streams stay bit-exact against an engine that has none of the three
    (whole budgets a round, no cache, nothing preempted) and against the
    plain forward over the whole sequence."""
    rng = np.random.default_rng(3)
    long_p = rng.integers(0, 64, (34,), np.int32)
    short_p = rng.integers(0, 64, (7,), np.int32)
    cb = _batcher(lm, use_kernel=False, max_len=96,
                  prefill_chunk=16, prefix_cache=True, n_pages=40)
    try:
        o1 = list(cb.submit(long_p, 8).result(timeout=300))
        hits0 = cb.prefix_cache.hits
        assert list(cb.submit(long_p, 8).result(timeout=300)) == o1
        assert cb.prefix_cache.hits > hits0     # the rounds share pages
        # 34 tokens in chunks of 16: three rounds; 32 of them shared: one
        assert cb.dispatch_kinds["mixed"] == 3 + 1
    finally:
        cb.shutdown()
    assert o1 == greedy_reference(lm, long_p, 8, 2, 2)[0]
    ref = _batcher(lm, use_kernel=False, max_len=96)
    try:
        assert list(ref.submit(long_p, 8).result(timeout=300)) == o1
        assert ref.dispatch_kinds["mixed"] == 2      # a budget of 32, then 2
    finally:
        ref.shutdown()
    # preemption: a higher-priority arrival evicts the lane; the resume
    # re-prefills through mixed rounds and stays bit-exact
    cb = _batcher(lm, use_kernel=False, lanes=1, decode_block=2)
    try:
        started = TokenGate()
        f1 = cb.submit(short_p, 20, priority=0, on_token=started)
        assert started.wait(timeout=120)
        f2 = cb.submit(long_p[:9], 4, priority=5)
        started.release()
        r2, r1 = f2.result(timeout=300), f1.result(timeout=300)
        assert cb.preemptions >= 1
    finally:
        cb.shutdown()
    ref = _batcher(lm, use_kernel=False, lanes=1)
    try:
        assert list(ref.submit(short_p, 20).result(timeout=300)) == list(r1)
        assert list(ref.submit(long_p[:9], 4).result(timeout=300)) == list(r2)
    finally:
        ref.shutdown()


def test_ragged_metrics_and_debug_state(lm):
    """GenerationMetrics picks up the ragged_dispatches counter and the
    per-kind dispatch label; debugz reports the plan."""
    pytest.importorskip("prometheus_client")
    from prometheus_client import CollectorRegistry

    from tpulab.utils.metrics import GenerationMetrics

    cb = _batcher(lm, use_kernel=False)
    m = GenerationMetrics(registry=CollectorRegistry())
    try:
        cb.submit(np.arange(5, dtype=np.int32) + 1, 6).result(timeout=300)
        m.poll(cb)
        dbg = cb.debug_state()["dispatch"]
        assert dbg["ragged_dispatches"] >= 1
        assert dbg["kinds"]["mixed"] >= 1
    finally:
        cb.shutdown()
    got = {s.name: s.value for fam in m.registry.collect()
           for s in fam.samples}
    assert got.get("tpulab_llm_ragged_dispatches_total", 0) >= 1
    kinds = {s.labels.get("kind"): s.value
             for fam in m.registry.collect() if fam.name.endswith("by_kind")
             for s in fam.samples if s.name.endswith("_total")}
    assert kinds.get("mixed", 0) >= 1


def test_use_kernel_chooses_the_attention_not_the_plan(lm):
    """Explicit ``use_kernel=False`` is the XLA gather under the one plan:
    the prompt rides a mixed round, which is the only dispatch counted
    among the ragged family's (the blocks' attention ran no kernel)."""
    cb = _batcher(lm, use_kernel=False)
    try:
        cb.submit(np.arange(5, dtype=np.int32) + 1, 4).result(timeout=300)
        assert not cb.use_kernel
        assert cb.dispatch_kinds["mixed"] == 1
        assert cb.dispatch_kinds["decode"] >= 1
        assert cb.ragged_dispatches == 1
    finally:
        cb.shutdown()
