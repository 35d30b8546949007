"""Pallas kernel tests (interpret mode on CPU; the same kernels compile for
TPU at serving time)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ------------------------------------- the ragged kernel, one query a lane --
def _paged_reference(q, k_pool, v_pool, tables, lengths):
    """Dense-gather reference (mirrors engine.paged_steps' XLA fallback
    math); handles GQA pools (Hkv < Hq) by repeating KV heads."""
    b, h, d = q.shape
    page_size, hkv = k_pool.shape[1], k_pool.shape[2]
    mp = tables.shape[1]
    k_ctx = jnp.repeat(k_pool[tables].reshape(b, mp * page_size, hkv, d),
                       h // hkv, axis=2)
    v_ctx = jnp.repeat(v_pool[tables].reshape(b, mp * page_size, hkv, d),
                       h // hkv, axis=2)
    scores = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) / np.sqrt(d)
    pos = jnp.arange(mp * page_size)
    mask = pos[None, None, :] <= lengths[:, None, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", probs, v_ctx.astype(jnp.float32))


def _single_query_attention(q, k_pool, v_pool, tables, lengths, **geometry):
    """The kernel that serves every token, in the single-query decode
    shape: ``q (B, Hq, D)`` is each lane's one query (``q_lens`` = 1) at
    position ``lengths[b]`` (``kv_lens`` = position + 1), over one layer
    of a page store in the engine's layout ``(1, P, 2, S, Hkv*D)``."""
    from tpulab.engine.kv_pool import kv_rows_view
    from tpulab.ops.ragged_attention import ragged_paged_attention
    pool = kv_rows_view(jnp.stack([k_pool, v_pool], axis=1))[None]
    lengths = jnp.asarray(lengths, jnp.int32)
    return ragged_paged_attention(
        q[:, None], pool, 0, jnp.asarray(tables, jnp.int32),
        jnp.ones_like(lengths), lengths + 1, **geometry)[:, 0]


def test_single_query_attention_matches_gather_reference():
    rng = jax.random.PRNGKey(0)
    b, h, d, pages, ps, mp = 3, 2, 16, 9, 8, 3
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (pages, ps, h, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (pages, ps, h, d), jnp.float32)
    # ragged: lanes at different lengths with distinct block tables
    # lengths include an exact page-start boundary (16 = 2*page_size): the
    # skip predicate must still attend the fresh page's first slot
    tables = jnp.asarray([[1, 2, 3], [4, 5, 7], [6, 0, 0]], jnp.int32)
    lengths = jnp.asarray([20, 16, 3], jnp.int32)
    got = _single_query_attention(q, k_pool, v_pool, tables, lengths)
    want = _paged_reference(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_single_query_attention_gqa_matches_expanded_reference():
    """GQA pools (Hkv < Hq): a query head reading its group's KV block in
    VMEM must match the dense reference with explicitly repeated KV
    heads."""
    rng = jax.random.PRNGKey(7)
    b, hq, hkv, d, pages, ps, mp = 3, 8, 2, 16, 10, 8, 3
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (pages, ps, hkv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (pages, ps, hkv, d), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 7], [6, 8, 9]], jnp.int32)
    lengths = jnp.asarray([21, 8, 2], jnp.int32)
    got = _single_query_attention(q, k_pool, v_pool, tables, lengths)
    want = _paged_reference(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_single_query_attention_long_context_exceeds_pipeline_depth():
    """Contexts with more BLOCKS than the DMA pipeline depth (nbuf slots)
    exercise the in-loop slot refill; a refill racing the slot it is about
    to read corrupts exactly this regime (blocks > nbuf), which the short
    tests above never reach.  g_pages/nbuf are pinned: the auto geometry
    would fold a test-sized context into one block."""
    rng = jax.random.PRNGKey(3)
    g_pages, nbuf = 2, 4
    mp = 2 * g_pages * nbuf + 3  # 19 pages = 10 blocks — past the pipeline
    b, h, d, ps = 2, 2, 16, 4
    pages = b * mp + 1
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (pages, ps, h, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (pages, ps, h, d), jnp.float32)
    tables = (1 + np.arange(b * mp, dtype=np.int32)).reshape(b, mp)
    lengths = jnp.asarray([mp * ps - 2, nbuf * ps + 1], jnp.int32)
    got = _single_query_attention(q, k_pool, v_pool, tables, lengths,
                                  g_pages=g_pages, nbuf=nbuf)
    want = _paged_reference(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_single_query_attention_partial_tail_block_poison():
    """A block whose tail pages are dead (beyond the lane's length, never
    DMA'd — stale VMEM) must not leak them into the output: the score
    side is masked, and V rides an explicit zeroing before its 0-weight
    sum (0 * garbage would still be garbage for inf/NaN)."""
    b, h, d, ps, mp = 1, 2, 8, 4, 4
    q = jnp.ones((b, h, d), jnp.float32)
    k_pool = jnp.zeros((6, ps, h, d), jnp.float32)
    v_pool = jnp.zeros((6, ps, h, d), jnp.float32)
    v_pool = v_pool.at[1].set(5.0)         # live page -> value 5
    k_pool = k_pool.at[2].set(jnp.inf)     # dead page IN the same block
    v_pool = v_pool.at[2].set(jnp.nan)
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    lengths = jnp.asarray([2], jnp.int32)  # 3 tokens: first page only
    # g_pages=4: one block spans live page 1 and poisoned pages 2/3
    out = _single_query_attention(q, k_pool, v_pool, tables, lengths,
                                  g_pages=4, nbuf=2)
    np.testing.assert_allclose(np.asarray(out), 5.0, rtol=1e-6)


def test_single_query_attention_skips_dead_pages():
    """Garbage in pages beyond a lane's length must not leak into output."""
    b, h, d, pages, ps = 1, 2, 8, 4, 4
    q = jnp.ones((b, h, d), jnp.float32)
    k_pool = jnp.zeros((pages, ps, h, d), jnp.float32)
    v_pool = jnp.zeros((pages, ps, h, d), jnp.float32)
    v_pool = v_pool.at[1].set(5.0)        # live page -> value 5
    k_pool = k_pool.at[2].set(1e6)        # dead page: poison K
    v_pool = v_pool.at[2].set(-1e6)       # dead page: poison V
    tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    lengths = jnp.asarray([2], jnp.int32)  # only first page, 3 tokens visible
    out = _single_query_attention(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), 5.0, rtol=1e-6)
