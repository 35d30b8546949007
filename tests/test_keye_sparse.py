"""Learned sparse attention: an indexer and its index rows beside the K/V
pages, attention over the keys it selects, softmax-routed experts.

A tiny decoder that keeps every ratio of ``keye_vl2`` (4 query heads on 2
KV heads with per-head QK-norm and RoPE, 4 index heads of 16 on one index
key, ``topk`` 12 — below the contexts the tests reach — and top-2 of 8
softmax-routed experts without a shared expert), held to the benchmark's
plain float32 reference (``perf/reference/keye_vl2.py``: a full ``I``, an
exact ``top_k``, a masked softmax, a loop over experts, nothing imported
from the program).
"""

import functools
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine.kv_pool import PagedKVPool, index_page_shape
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_ffn_block, _sparse_attention,
                                       pack_round, paged_decode_block,
                                       paged_decode_step, paged_mixed_step,
                                       paged_ragged_forward)
from tpulab.models.spec import (ModelSpec, dense_spec, init_params,
                                keye_vl2_spec)
from tpulab.ops import sparse_attention as sa

from helpers_engine import FirstTokenGate
from helpers_steps import decode_block, mixed_step
from helpers_attention import (BF16_ATOL, BF16_RTOL, assert_parents_bits,
                               sparse_attend_case, sparse_decode_case)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, LANES, PAGE, TOPK = 97, 4, 8, 12
CONFIG = {
    "model_type": "KeyeVL2", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e4,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "attention_bias": False,
    "use_sliding_window": False,
    "rope_scaling": {"mrope_section": [4, 6, 6], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": TOPK},
    "vocab_size": VOCAB,
}
i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731
BOTH = pytest.mark.parametrize("use_kernel", [False, True],
                               ids=["xla", "kernels-interpret"])


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "keye_vl2.py")
    spec = importlib.util.spec_from_file_location("ref_keye_vl2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    spec = keye_vl2_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, 0, seed=3, scale=0.3)


def _kw(spec, use_kernel=False):
    return dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
                compute_dtype=jnp.float32, use_kernel=use_kernel, spec=spec)


@functools.lru_cache(maxsize=None)
def _jit(fn, spec, use_kernel=False, **static):
    """The step function jitted (a test's rounds and steps share compiled
    programs instead of running operation by operation)."""
    return jax.jit(functools.partial(fn, **static, **_kw(spec, use_kernel)))


def _fresh(spec, pages_a_lane=6):
    """``(kv_pool pair, tables)``: K/V pages and index rows under the same
    page ids, filled with junk (what a reused page holds)."""
    pool = PagedKVPool(n_pages=1 + pages_a_lane * LANES, page_size=PAGE,
                       n_layers=spec.n_layers, n_heads=spec.n_kv_heads,
                       head_dim=spec.head_dim, dtype=jnp.float32,
                       index_dim=spec.index_dim)
    tables = i32(1 + np.arange(pages_a_lane * LANES).reshape(LANES, -1))
    return (jnp.full(pool.kv.shape, 3.0, jnp.float32),
            jnp.full(pool.index.shape, -2.0, jnp.float32)), tables


def _round(spec, params, store, tables, prefill, decode, lengths,
           use_kernel=False):
    toks, row_lane, row_off, q_lens = pack_round(LANES, prefill, decode)
    kv_lens = np.asarray(lengths, np.int32) + q_lens
    kv_lens[q_lens == 0] = 0          # as the scheduler leaves idle lanes
    _nt, _lp, last, store, _moe = mixed_step(
        _jit(paged_mixed_step, spec, use_kernel, lanes=LANES,
             max_pages=tables.shape[1]),
        params, store, tables, toks, row_lane, row_off, q_lens, kv_lens,
        spec=spec)
    return np.asarray(last), store


def _lane_rows(store, tables, lane, n):
    """The first ``n`` K/V rows and index rows of a lane, all layers."""
    pages, index = (np.asarray(a) for a in store)
    ids = np.asarray(tables)[lane]
    kv = pages[:, ids].transpose(0, 2, 1, 3, 4).reshape(
        pages.shape[0], 2, -1, pages.shape[-1])[:, :, :n]
    return kv, index[:, ids].reshape(index.shape[0], -1, index.shape[-1])[:, :n]


# ------------------------------------------------------------------ the spec ----

def test_spec_reads_the_published_keys_and_sa_config():
    with open(os.path.join(ROOT, "perf", "configs", "keyevl2-l6.json")) as f:
        spec = keye_vl2_spec(json.load(f))
    assert (spec.n_layers, spec.d_model, spec.n_heads, spec.n_kv_heads,
            spec.head_dim) == (6, 2048, 32, 4, 128)
    assert (spec.index_heads, spec.index_dim, spec.index_topk) == (16, 64,
                                                                   2048)
    assert (spec.n_experts, spec.top_k, spec.moe_ff, spec.n_shared) == (
        128, 8, 768, 0)
    assert spec.router == "softmax" and spec.qk_norm
    assert spec.layer_kinds == ("moe",) * 6 and spec.cache_entry == "kv_index"
    assert spec.rope_theta == 1e7 and spec.rms_eps == 1e-6


def test_new_fields_default_to_what_the_other_specs_mean():
    for spec in (dense_spec(64, 4, 2), ModelSpec(n_layers=1, d_model=8,
                                                 n_heads=1)):
        assert (spec.index_topk, spec.qk_norm, spec.router) == (
            0, False, "sigmoid_bias")
        assert spec.cache_entry == "kv"
    with pytest.raises(ValueError, match="router"):
        ModelSpec(n_layers=1, d_model=8, n_heads=1, router="hash")
    with pytest.raises(ValueError, match="index_heads"):
        ModelSpec(n_layers=1, d_model=8, n_heads=1, index_topk=4)
    with pytest.raises(ValueError, match="GQA"):
        ModelSpec(n_layers=1, d_model=8, n_heads=1, attention="mla",
                  index_topk=4, index_heads=1, index_dim=8)


@pytest.mark.parametrize("key, value", [
    ("mlp_only_layers", [1]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("attention_bias", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("norm_topk_prob", False),
    ("sa_config", dict(CONFIG["sa_config"], indexer_num_kv_heads=2))],
    ids=["mlp_only_layers", "decoder_sparse_step", "use_sliding_window",
         "attention_bias", "rope_scaling", "norm_topk_prob",
         "indexer_num_kv_heads"])
def test_keys_the_block_does_not_compute_are_refused_by_name(key, value,
                                                             request):
    with pytest.raises(ValueError, match=request.node.callspec.id):
        keye_vl2_spec(dict(CONFIG, **{key: value}))


def test_init_params_draws_the_tree_the_block_reads(model):
    spec, params = model
    p = params["layer0"]
    assert set(p) == {"ln1", "ln2", "wqkv", "wo", "q_norm", "k_norm",
                      "indexer", "moe"}
    assert set(p["moe"]) == {"router", "w13", "w2"}
    assert set(p["indexer"]) == {"wq", "wk", "k_norm", "ww"}
    assert p["indexer"]["wq"].shape == (64, 4 * 16)
    assert p["indexer"]["k_norm"]["bias"].shape == (16,)
    assert p["q_norm"]["scale"].shape == (32,) and "lm_head" in params


# ------------------------------------------------------------------ the pool ----

def test_index_rows_share_the_page_ids_and_count_in_the_bytes():
    pool = PagedKVPool(n_pages=9, page_size=PAGE, n_layers=2, n_heads=2,
                       head_dim=32, dtype=jnp.float32, index_dim=16)
    assert pool.entry_kind == "kv_index"
    assert pool.index.shape == (2, 9) + index_page_shape(PAGE, 16)
    assert index_page_shape(16, 64) == (16, 128)
    # K and V of 2 heads x 32, and one index row padded to 128, float32
    assert pool.index_bytes_per_token == 2 * 128 * 4
    assert pool.bytes_per_token == 2 * (2 * 2 * 32 + 128) * 4
    assert pool.hbm_bytes == pool.bytes_per_token * 9 * PAGE
    page = pool.allocate_page()
    pool.index = pool.index.at[0, page].set(1.0)
    pool.reset()
    assert float(jnp.abs(pool.index).sum()) == 0 and pool.free_pages == 8
    with pytest.raises(NotImplementedError, match="elastic"):
        pool.grow(4)
    with pytest.raises(NotImplementedError, match="K/V pages only"):
        pool.host_shape(1)
    pool.close()
    assert pool.hbm_bytes == 0 and pool.index is None
    plain = PagedKVPool(n_pages=3, page_size=PAGE, n_layers=1, n_heads=2,
                        head_dim=32)
    assert plain.index is None and plain.index_bytes_per_token == 0


# -------------------------------------------------------------- the selection ----

def test_selection_is_top_k_where_no_scores_tie():
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.standard_normal((70, 256)), jnp.float32)
    live = jnp.asarray(np.arange(256)[None, :] <= rng.integers(
        0, 256, 70)[:, None])
    for k in (1, 12, 100):
        got = np.asarray(sa.select_topk(scores, live, k))
        vals, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), k)
        want = np.zeros_like(got)
        np.put_along_axis(want, np.asarray(idx), np.asarray(vals) > -np.inf,
                          axis=1)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            got.sum(1), np.minimum(k, np.asarray(live).sum(1)))


@pytest.mark.parametrize("rows", [5, 80], ids=["radix16", "bisect"])
def test_tied_scores_select_exactly_k_the_lower_key_first(rows):
    rng = np.random.default_rng(1)
    # few distinct values (zeros and negatives among them): many ties at
    # the k-th score
    scores = jnp.asarray(rng.integers(-2, 3, (rows, 128)), jnp.float32)
    live = jnp.asarray(np.arange(128)[None, :] < rng.integers(
        1, 129, rows)[:, None])
    got = np.asarray(sa.select_topk(scores, live, 16))
    vals, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), 16)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), np.asarray(vals) > -np.inf,
                      axis=1)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(16, np.asarray(live).sum(1))).all()
    assert not (got & ~np.asarray(live)).any()


def test_the_selection_attended_is_the_references_top_k_set(model, reference):
    """Index queries, keys and weights of a 40-token sequence through the
    program's scores and selection and through the reference's full ``I``
    and exact ``top_k``: the same set for every token."""
    rng = np.random.default_rng(2)
    t, hi, di = 40, 4, 16
    a = jnp.asarray(rng.standard_normal((t, hi, di)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((t, di)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((t, hi)), jnp.float32)
    want = np.asarray(reference.selection(
        reference.index_scores(a, b, c, 8, 8), TOPK))
    ictx = jnp.zeros((2, 48, 128), jnp.float32).at[1, :t, :di].set(b)
    lane = jnp.ones((t,), jnp.int32)
    live = jnp.arange(48)[None, :] <= jnp.arange(t)[:, None]
    for use_kernel in (False, True):
        scores = sa.index_scores(a, c * (hi * di) ** -0.5, lane, ictx,
                                 i32([0, 1]), i32([0, t]), use_kernel)
        got = np.asarray(sa.select_topk(scores, live, TOPK))
        np.testing.assert_array_equal(got[:, :t], want)
        assert not got[:, t:].any()
        assert (got.sum(1) == np.minimum(np.arange(t) + 1, TOPK)).all()


# ---------------------------------------------------------------- the kernels ----

def test_score_kernel_in_interpret_mode_matches_the_xla_form():
    """Rows of three lanes (one lane without a row, one row without a
    token), contexts that end inside, at and before a key block."""
    rng = np.random.default_rng(3)
    r, hi, di, w = 11, 4, 16, 64
    a = jnp.asarray(rng.standard_normal((r, hi, di)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((r, hi)), jnp.float32)
    ictx = jnp.asarray(rng.standard_normal((4, w, 128)), jnp.float32)
    lane = i32([0, 0, 0, 2, 2, 3, -1, 3, 3, 3, 0])
    kv_lens = np.array([64, 9, 20, 33])
    want = np.asarray(sa.index_scores_xla(a, c, lane, ictx))
    got = np.asarray(sa.index_scores(a, c, lane, ictx, i32([1, 0, 1, 1]),
                                     i32(kv_lens), True))
    for row, ln in enumerate(np.asarray(lane)):
        if ln >= 0:
            n = kv_lens[ln]
            np.testing.assert_allclose(got[row, :n], want[row, :n],
                                       rtol=2e-5, atol=2e-5)


def test_attention_kernel_in_interpret_mode_matches_the_xla_form():
    args = sparse_attend_case()
    want = np.asarray(sa.sparse_attend(*args, use_kernel=False))
    got = np.asarray(sa.sparse_attend(*args, use_kernel=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not got[3].any() and not got[5].any()


def test_decode_kernel_in_interpret_mode_matches_the_xla_form():
    args, as_rows = sparse_decode_case()
    want = np.asarray(sa.sparse_attend(*as_rows, use_kernel=False))
    got = np.asarray(sa.sparse_attend_decode(*args))
    for lane in (0, 2, 3):
        np.testing.assert_allclose(got[lane], want[lane], rtol=2e-5,
                                   atol=2e-5)
    assert not got[3].any()


@pytest.mark.parametrize("kernel", ["sparse_paged_attention",
                                    "sparse_paged_decode"])
def test_float32_store_gives_the_parents_bits(kernel):
    """A float32 store keeps both products at ``HIGHEST``: the outputs of
    the commit before the operand rule, to the bit."""
    if kernel == "sparse_paged_attention":
        got = sa.sparse_attend(*sparse_attend_case(), use_kernel=True)
    else:
        got = sa.sparse_attend_decode(*sparse_decode_case()[0])
        got = got.at[1].set(0.0)  # the skipped lane's row is unwritten
    assert_parents_bits(kernel, got)


@pytest.mark.parametrize("kernel", ["sparse_paged_attention",
                                    "sparse_paged_decode"])
def test_bf16_store_rounds_where_the_xla_form_does(kernel):
    """A bf16 store: the kernel rounds the probabilities to bf16 before the
    value product, as ``sparse_attend_xla`` does, so the two agree to the
    rounding of their bf16 outputs (the interpreter shows half of this)."""
    args, as_rows = sparse_decode_case(jnp.bfloat16)
    if kernel == "sparse_paged_attention":
        as_rows = sparse_attend_case(jnp.bfloat16)
        got = sa.sparse_attend(*as_rows, use_kernel=True)
        rows = np.asarray(as_rows[2]) >= 0
    else:
        got = sa.sparse_attend_decode(*args)
        rows = np.asarray(args[5]) > 0
    want = sa.sparse_attend(*as_rows, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got, np.float32)[rows],
                               np.asarray(want, np.float32)[rows],
                               rtol=BF16_RTOL, atol=BF16_ATOL)


# ---------------------------------------------------------- the layer's halves ----

@BOTH
@pytest.mark.parametrize("t", [9, 30], ids=["below-topk", "above-topk"])
def test_sparse_attention_matches_the_plain_references_layer(model, reference,
                                                             t, use_kernel):
    spec, params = model
    p = params["layer1"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((t, 64)),
                    jnp.float32)
    hyper = reference.hyper_of(CONFIG)
    want = reference._attention(
        x, {k: p[k] for k in ("ln1", "wqkv", "q_norm", "k_norm", "indexer",
                              "wo")},
        eps=1e-6, theta=1e4, n_heads=4, n_kv_heads=2, head_dim=32,
        index_heads=4, index_dim=16, topk=TOPK, q_chunk=8, kv_chunk=8,
        block=16) - x
    store, tables = _fresh(spec)
    h = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6))[None]
    pos = jnp.arange(t)[None]
    seg = dict(tables=tables[2:3], q_lens=i32([t]), kv_lens=i32([t]),
               use_kernel=use_kernel, kernel_geometry=None, mesh=None)
    attn, store = _sparse_attention(
        spec, p, 1, h, pos, jnp.ones((1, t), bool), store,
        tables[2][pos // PAGE], pos % PAGE, seg, jnp.float32)
    np.testing.assert_allclose(np.asarray(attn[0] @ p["wo"]),
                               np.asarray(want), rtol=3e-4, atol=3e-5)
    assert hyper["index_topk"] == TOPK


def test_softmax_routing_without_a_shared_expert_matches_the_expert_loop(
        model, reference):
    spec, params = model
    p = params["layer0"]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 7, 64)),
                    jnp.float32)
    got, stats = _ffn_block(spec, p, 0, x, jnp.ones((2, 7), bool),
                            jnp.float32)
    want = reference._ffn(x.reshape(14, 64), p, eps=1e-6, top_k=2, norm=True)
    np.testing.assert_allclose(np.asarray(got).reshape(14, 64),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    assert int(stats[:8].sum()) == 14 * 2 and "shared" not in p


# ------------------------------------------------------- the step programs ----

@BOTH
def test_one_chunk_uneven_chunks_and_token_by_token_agree(model, reference,
                                                          use_kernel):
    """A 29-token prompt (past ``topk`` 12) through mixed rounds in one
    chunk, in chunks of 16, 2, 1 and 10 (a chunk that ends on a page's edge,
    two short ones, one that crosses an edge; round widths the other tests
    of this file compile anyway), and token by token through decode steps:
    the same logits at the last position, the same K/V and index rows, and
    the reference's logits."""
    spec, params = model
    tokens = np.random.default_rng(7).integers(0, VOCAB, 29)
    want = reference.last_logits(params, tokens.tolist(), 1,
                                 **reference.hyper_of(CONFIG))[0]
    outs = []
    for sizes in ([29], [16, 2, 1, 10]):
        store, tables = _fresh(spec)
        at = 0
        for n in sizes:
            last, store = _round(spec, params, store, tables,
                                 {1: tokens[at:at + n]}, {}, [0, at, 0, 0],
                                 use_kernel)
            at += n
        outs.append((last[1], _lane_rows(store, tables, 1, 29)))
    store, tables = _fresh(spec)
    for pos, tok in enumerate(tokens):
        logits, store, _moe = _jit(paged_decode_step, spec, use_kernel)(
            params, store, tables, i32([0, pos, 0, 0]), i32([0, tok, 0, 0]),
            jnp.asarray([False, True, False, False]))
    outs.append((np.asarray(logits)[1], _lane_rows(store, tables, 1, 29)))
    for logits, (kv, index) in outs:
        np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(kv, outs[0][1][0], rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(index, outs[0][1][1], rtol=3e-5,
                                   atol=3e-5)
    # the index key is the first 16 columns of its row, the rest zeros
    assert not outs[0][1][1][..., 16:].any()
    assert np.abs(outs[0][1][1][..., :16]).min() > 0
    # pages of the lanes that ran nothing still hold what they held
    assert (_lane_rows(store, tables, 0, 8)[1] == -2).all()


@BOTH
def test_a_round_of_several_lanes_is_each_lane_alone(model, use_kernel):
    """Two lanes' chunks (a later chunk past ``topk``, a first chunk) and two
    other lanes' decode rows in ONE packed round give, lane for lane, the
    logits of four rounds that carry one lane each."""
    spec, params = model
    rng = np.random.default_rng(8)
    seqs = [rng.integers(0, VOCAB, n) for n in (26, 19, 15, 17)]

    def warm(store, tables):
        """Lanes 0, 1 and 3 have a past: 15, 18 and 16 tokens."""
        return _round(spec, params, store, tables,
                      {0: seqs[0][:15], 1: seqs[1][:18], 3: seqs[3][:16]}, {},
                      [0, 0, 0, 0], use_kernel)[1]

    lengths = [15, 18, 0, 16]
    prefill = {0: seqs[0][15:26], 2: seqs[2]}
    decode = {1: int(seqs[1][18]), 3: int(seqs[3][16])}
    store, tables = _fresh(spec)
    together, _ = _round(spec, params, warm(store, tables), tables, prefill,
                         decode, lengths, use_kernel)
    for lane in range(LANES):
        alone, tables = _fresh(spec)
        last, _ = _round(
            spec, params, warm(alone, tables), tables,
            {k: v for k, v in prefill.items() if k == lane},
            {k: v for k, v in decode.items() if k == lane}, lengths,
            use_kernel)
        np.testing.assert_allclose(together[lane], last[lane], rtol=3e-5,
                                   atol=3e-5)


def test_the_packed_round_is_the_padded_form(model):
    """The same segments through ``paged_ragged_forward(last_only=True)``."""
    spec, params = model
    rng = np.random.default_rng(9)
    store, tables = _fresh(spec)
    prefill = {0: rng.integers(0, VOCAB, 20), 3: rng.integers(0, VOCAB, 7)}
    packed, _ = _round(spec, params, store, tables, prefill, {}, [0] * 4)
    seq = np.zeros((LANES, 20), np.int32)
    for lane, chunk in prefill.items():
        seq[lane, :len(chunk)] = chunk
    q_lens = i32([20, 0, 0, 7])
    store, tables = _fresh(spec)
    padded, _store, _moe = _jit(paged_ragged_forward, spec, last_only=True)(
        params, store, tables, i32(seq), q_lens, q_lens)
    for lane in prefill:
        np.testing.assert_allclose(packed[lane], np.asarray(padded)[lane],
                                   rtol=3e-5, atol=3e-5)


def test_one_block_of_eight_is_eight_blocks_of_one(model):
    """Decode blocks through the index rows, a lane that stops inside the
    block among them: K = 8 against eight K = 1."""
    spec, params = model
    rng = np.random.default_rng(10)
    prompts = {0: rng.integers(0, VOCAB, 14), 2: rng.integers(0, VOCAB, 21)}

    def run(k):
        store, tables = _fresh(spec)
        _last, store = _round(spec, params, store, tables, prompts, {},
                              [0] * 4)
        carry = ([14, 0, 21, 0], [5, 0, 9, 0], [True, False, True, False],
                 [8, 0, 3, 0])
        block = _jit(paged_decode_block, spec, k=k, lanes=LANES,
                     max_pages=tables.shape[1])
        out = []
        for i in range(8 // k):
            # a chain: the first block's state in its buffer, then the carry
            toks, _lps, ems, carry, store, _moe = decode_block(
                block, params, store, tables, carry, k, fresh=i == 0,
                spec=spec)
            out.append(np.where(ems, toks, -1))
        return np.concatenate(out, axis=1), _lane_rows(store, tables, 2, 24)

    (t8, (kv8, ix8)), (t1, (kv1, ix1)) = run(8), run(1)
    np.testing.assert_array_equal(t8, t1)
    assert (t8[2, 3:] == -1).all() and (t8[0] >= 0).all()
    np.testing.assert_allclose(kv8, kv1, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(ix8, ix1, rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------------ the engine ----

def _engine(spec, params, **kw):
    kw = dict(dict(lanes=3, max_len=64, page_size=PAGE,
                   compute_dtype=jnp.float32, prefill_chunk=8), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             **kw)


@BOTH
def test_tiny_model_end_to_end_against_the_reference(model, reference,
                                                     use_kernel):
    """Four chunks of 8 through mixed rounds, then decode blocks, every
    context past ``topk`` from the second chunk on: every emitted token's
    log-probability is the reference's; the counters are the lengths'."""
    spec, params = model
    cb = _engine(spec, params, use_kernel=use_kernel)
    try:
        assert cb.use_kernel == use_kernel
        pool = cb.debug_state()["pool"]
        assert pool["entry_kind"] == "kv_index"
        assert pool["index_bytes_per_token"] == 2 * 128 * 4
        assert pool["bytes_per_token"] == 2 * (2 * 2 * 32 + 128) * 4
        prompt = np.random.default_rng(11).integers(0, VOCAB, 29).tolist()
        toks, lps = cb.submit(prompt, steps=10, logprobs=True).result(
            timeout=300)
        got = reference.compare(params, prompt, toks, lps,
                                **reference.hyper_of(CONFIG))
        assert got["logprob_err_max"] < 2e-4 and got["argmax_gap"] == 0
        s = cb.debug_state()["sparse"]
        assert s["topk"] == TOPK
        total = {k: sum(v.values()) for k, v in s.items() if k != "topk"}
        # 29 prompt rows and 9 decode rows (the tenth token is not fed
        # back), two layers: contexts 1 .. 38
        ctx = np.arange(1, 39)
        assert total["query_rows"] == 2 * 38
        assert total["keys_scored"] == 2 * ctx.sum()
        assert total["keys_attended"] == 2 * np.minimum(ctx, TOPK).sum()
        assert total["dense_rows"] == 2 * TOPK
        assert s["query_rows"]["decode"] > 0 and s["dense_rows"]["decode"] == 0
        assert cb.debug_state()["moe"]["expert_layers"] == [0, 1]
    finally:
        cb.shutdown()


def _fresh_tokens(spec, params, prompt, steps):
    cb = _engine(spec, params, lanes=1)
    try:
        return cb.submit(prompt, steps).result(timeout=300)
    finally:
        cb.shutdown()


def test_run_ahead_chain_gives_the_tokens_of_the_chain_without_it(
        model, monkeypatch):
    spec, params = model
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (9, 15, 6)]

    def run(chain):
        if not chain:
            monkeypatch.setattr(ContinuousBatcher, "_chain_block",
                                lambda self, stash, jnp, ahead: (None, "k1"))
        cb = _engine(spec, params)
        try:
            futs = [cb.submit(p, 24) for p in prompts]
            return [f.result(timeout=300) for f in futs], cb.ahead_blocks
        finally:
            cb.shutdown()
            monkeypatch.undo()

    chained, ahead = run(True)
    plain, none_ahead = run(False)
    assert ahead > 0 and none_ahead == 0
    assert chained == plain
    assert chained == [_fresh_tokens(spec, params, p, 24) for p in prompts]


def test_a_reused_lane_gives_the_tokens_of_a_fresh_engine(model):
    """Four requests through ONE lane: each reads only its own index rows
    and K/V, whatever its predecessor left in the pages it takes over."""
    spec, params = model
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (19, 5, 23, 8)]
    cb = _engine(spec, params, lanes=1)
    try:
        got = [cb.submit(p, 9).result(timeout=300) for p in prompts]
    finally:
        cb.shutdown()
    assert got == [_fresh_tokens(spec, params, p, 9) for p in prompts]


def test_a_preempted_request_resumes_with_a_fresh_engines_tokens(model):
    spec, params = model
    rng = np.random.default_rng(14)
    p_low, p_hi = (rng.integers(0, VOCAB, n).tolist() for n in (17, 6))
    cb = _engine(spec, params, lanes=1)
    try:
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 14, on_token=started)
        assert started.wait(timeout=120)
        f_hi = cb.submit(p_hi, 5, priority=10)
        started.release()
        got_hi, got_low = f_hi.result(timeout=300), f_low.result(timeout=300)
        assert cb.preemptions >= 1
    finally:
        cb.shutdown()
    assert got_low == _fresh_tokens(spec, params, p_low, 14)
    assert got_hi == _fresh_tokens(spec, params, p_hi, 5)


@pytest.mark.parametrize("option", [
    dict(prefix_cache=True), dict(kv_offload=True),
    dict(kv_offload=True, kv_publish=True), dict(hbm=object()),
    dict(draft_params={"layer0": {}}), dict(mesh=object()),
    dict(kv_dtype=jnp.float16)],
    ids=["prefix_cache", "kv_offload", "kv_publish", "hbm", "draft_params",
         "mesh", "kv_dtype"])
def test_options_the_index_rows_do_not_carry_are_refused_by_name(
        model, option, request):
    spec, params = model
    with pytest.raises(NotImplementedError, match=request.node.callspec.id):
        _engine(spec, params, **option)


def test_a_pool_without_index_rows_is_refused(model):
    spec, params = model
    pool = PagedKVPool(n_pages=9, page_size=PAGE, n_layers=2, n_heads=2,
                       head_dim=32, dtype=jnp.float32)
    with pytest.raises(ValueError, match="index rows"):
        _engine(spec, params, pool=pool)


# ------------------------------ the kernels at the published widths, Mosaic ----

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [8, 256, 520],
                         ids=["decode", "chunk", "chunk-512"])
def test_mosaic_compiles_the_score_kernel_at_the_published_widths(one_chip,
                                                                  rows):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lanes, width = 8, 32768
    compiled = jax.jit(lambda *a: sa._index_scores(*a, False)).lower(
        shape(rows, 16, 64), shape(rows, 16, dtype=jnp.float32),
        shape(rows, dtype=jnp.int32), shape(lanes, width, 128),
        shape(lanes, dtype=jnp.int32), shape(lanes, dtype=jnp.int32)
    ).compile()
    assert "dsa_index_scores" in compiled.as_text()


@pytest.mark.parametrize("rows", [8, 64, 520],
                         ids=["decode", "chunk-64", "chunk-512"])
def test_mosaic_compiles_the_attention_kernel_at_the_published_widths(
        one_chip, rows):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lanes, max_pages = 8, 2048
    compiled = jax.jit(lambda *a: sa._sparse_attn(*a, False)).lower(
        shape(rows, 32, 128), shape(rows, max_pages * 16, dtype=jnp.bool_),
        shape(rows, dtype=jnp.int32), shape(6, 12289, 2, 16, 512),
        shape(1, dtype=jnp.int32), shape(lanes, max_pages, dtype=jnp.int32),
        shape(lanes, dtype=jnp.int32), shape(lanes, dtype=jnp.int32)
    ).compile()
    assert "sparse_paged_attention" in compiled.as_text()


@pytest.mark.parametrize("lanes,h,hkv,d,max_pages,pool", [
    (8, 32, 8, 128, 512, (16, 1025)),
    (32, 20, 1, 128, 512, (2, 8193)),
    (32, 16, 2, 256, 1024, (2, 20481)),
], ids=["mistral7b-l16-g4", "jamba2-3b-g20", "qwen3next-l8-ep4-g8-d256"])
def test_mosaic_compiles_the_one_row_kv_kernel_at_the_cells_widths(
        one_chip, lanes, h, hkv, d, max_pages, pool):
    """The dense K/V walk at one row a lane slices a KV head's ``g`` query
    heads out of its ``(H, D)`` block (here, beside the sparse walk that
    shares its block function: one file describes the chip): groups of 4,
    20 and 8 rows of bf16 are no whole tiles, and Mosaic takes them."""
    from tpulab.ops.ragged_attention import _ragged_attn

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    i32s = lambda *dims: shape(*dims, dtype=jnp.int32)      # noqa: E731
    text = _ragged_attn.lower(
        shape(lanes, 1, h, d), shape(*pool, 2, 16, hkv * d), i32s(1),
        i32s(lanes, max_pages), i32s(lanes), i32s(lanes),
        interpret=False).compile().as_text()
    assert "ragged_paged_decode" in text
    assert "ragged_paged_attention" not in text
