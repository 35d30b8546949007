"""ZAYA1 through the paged engine: compressed convolutional attention on K/V
pages WITH a lane state in the same layer, behind an MLP router with depth
averaging, top-1 of 16 experts and a skip column, residual scaling around
both sublayers.

Tiny widths that keep every ratio of ``zaya`` (8 query heads on 2 KV heads,
taps 2 and 2, half a head roped, 17 router columns at top-1, 3 layers), held
to the benchmark's plain float32 reference (``perf/reference/zaya.py``: one
causal forward over the whole sequence, the convolutions as shifts, a loop
over experts, nothing imported from the program) and to computations written
out by hand.
"""

import dataclasses
import importlib.util
import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_engine_plan
from helpers_engine import FirstTokenGate
from helpers_steps import decode_block, mixed_step
from tpulab.engine.kv_pool import (LaneStateStore, PagedKVPool,
                                   lane_state_shapes)
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_ffn_block, _residual,
                                       _segment_conv, _segment_window,
                                       pack_round, paged_decode_block,
                                       paged_decode_step, paged_mixed_step,
                                       paged_ragged_forward)
from tpulab.models.spec import ModelSpec, init_params, zaya_leaf, zaya_spec
from tpulab.parallel.moe import route

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, LANES, PAGE = 97, 4, 8
WIDTH = 16          # prompt rows of a packed round here (``_round``)
CONFIG = {
    "model_type": "zaya", "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "cca_time0": 2, "cca_time1": 2,
    "partial_rotary_factor": 0.5, "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"}, "rope_type": "default"},
    "layer_types": ["hybrid"] * 3, "num_hidden_layers": 3, "num_experts": 16,
    "num_experts_per_tok": 1, "moe_intermediate_size": 32,
    "router_hidden_size": 16, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "attention_bias": False, "lm_head_bias": False, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": VOCAB,
}
#: the published configuration as the benchmark cuts it (ISSUE 54)
PUBLISHED = dict(
    CONFIG, hidden_size=2048, head_dim=128, moe_intermediate_size=2048,
    router_hidden_size=256, num_hidden_layers=16, layer_types=["hybrid"] * 40,
    vocab_size=262272)
i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731
f64 = lambda x: np.asarray(x, np.float64)      # noqa: E731


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "zaya.py")
    spec = importlib.util.spec_from_file_location("ref_zaya", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    plain = module.last_logits

    def bucketed(params, tokens, n_last, *, stores=False, **hyper):
        """``last_logits`` over ``tokens`` and zeros behind them up to a
        multiple of 16: no position of a causal forward sees what follows
        it, and the reference compiles once a length (1.4 s each here).  A
        call that asks for the stores is left as it is: the tails are the
        LAST token's."""
        pad = -len(tokens) % 16
        if stores or not pad:
            return plain(params, tokens, n_last, stores=stores, **hyper)
        return plain(params, list(tokens) + [0] * pad, n_last + pad,
                     **hyper)[:n_last]
    module.last_logits = bucketed
    return module


@pytest.fixture(scope="module")
def model():
    spec = zaya_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, 0, seed=3, scale=0.1)


def _kw(spec, use_kernel=False):
    return dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
                compute_dtype=jnp.float32, use_kernel=use_kernel, spec=spec)


def _fresh(spec, junk=False):
    """``(kv_pool pair, tables)``: a page store and a lane-state store of
    every layer, the latter filled with junk on request (what a lane holds
    after another sequence ran in it)."""
    pool = PagedKVPool(n_pages=1 + 4 * LANES, page_size=PAGE,
                       n_layers=len(spec.attention_layers),
                       n_heads=spec.n_kv_heads, head_dim=spec.head_dim,
                       dtype=jnp.float32)
    state = LaneStateStore(spec, LANES, jnp.float32).arrays
    if junk:
        state = tuple(jnp.full(a.shape, 3.0, a.dtype) for a in state)
    tables = i32(1 + np.arange(4 * LANES).reshape(LANES, 4))
    return (pool.kv, state), tables


@lru_cache(maxsize=None)
def _jitted(fn, spec, use_kernel, **kw):
    return jax.jit(partial(fn, **_kw(spec, use_kernel), **kw))


def _round(spec, params, store, tables, prefill, decode, lengths,
           use_kernel=False, **kw):
    """One ``paged_mixed_step``: ``prefill`` {lane: chunk}, ``decode`` {lane:
    token}, ``lengths`` the lanes' positions before it.  Returns ``(last
    logits (LANES, vocab), store, ...)`` as ``mixed_step`` orders the rest."""
    toks, row_lane, row_off, q_lens = pack_round(LANES, prefill, decode)
    # every round of this file at ONE width (rows without a token between
    # the chunks and the decode rows): one program a spec and a plan
    pad = WIDTH + LANES - len(toks)
    toks, row_lane, row_off = (
        np.insert(a, len(a) - LANES, np.full(pad, fill, np.int32))
        for a, fill in ((toks, 0), (row_lane, -1), (row_off, 0)))
    kv_lens = np.asarray(lengths, np.int32) + q_lens
    kv_lens[q_lens == 0] = 0          # as the scheduler leaves idle lanes
    _nt, _lp, last, *rest = mixed_step(
        _jitted(paged_mixed_step, spec, use_kernel, lanes=LANES, max_pages=4),
        params, store, tables, toks, row_lane, row_off, q_lens, kv_lens,
        spec=spec, **kw)
    return (np.asarray(last), *rest)


def _tails(store, lane):
    """The lane's three tails, every layer: ``(layers, kept, C)`` each."""
    return [np.asarray(t[:, :, lane]) for t in store[1]]


# ------------------------------------------------------------- the spec ----

def test_spec_reads_the_published_keys():
    spec = zaya_spec(PUBLISHED)
    assert (spec.n_layers, spec.d_model, spec.n_heads, spec.n_kv_heads,
            spec.head_dim) == (16, 2048, 8, 2, 128)
    assert spec.cca_taps == (2, 2) and spec.res_scale
    assert (spec.rotary_dim, spec.rope_theta, spec.rms_eps) == (64, 5e6, 1e-5)
    assert (spec.router, spec.router_width) == ("mlp", 256)
    assert (spec.n_experts, spec.zero_experts, spec.ffn_experts, spec.top_k,
            spec.moe_ff, spec.norm_topk, spec.n_shared) == (17, 1, 16, 1,
                                                            2048, False, 0)
    # a CCA layer owns a layer of BOTH stores
    assert spec.mixers == ("cca",) * 16 and spec.state_kind == "cca"
    assert spec.state_layers == spec.attention_layers == tuple(range(16))
    assert [spec.store_layer(i) for i in (0, 7, 15)] == [0, 7, 15]
    assert spec.cache_entry == "kv" and spec.layer_kinds == ("moe",) * 16
    hash(spec)     # it keys the jit memo


@pytest.mark.parametrize("key, value", [
    ("layer_types", ["hybrid", "hybrid_sliding", "hybrid"]),
    ("sliding_window", 4096), ("attention_bias", True),
    ("lm_head_bias", True), ("tie_word_embeddings", False),
    ("hidden_act", "gelu"),
    ("rope_parameters", {"hybrid": {"rope_theta": 1e4, "rope_type": "yarn"}})])
def test_spec_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        zaya_spec(dict(CONFIG, **{key: value}))


BASE = dict(n_layers=2, d_model=16, n_heads=4, n_kv_heads=2, head_dim=8)


@pytest.mark.parametrize("kw, match", [
    (dict(attention="mla", n_kv_heads=0, head_dim=0, q_lora_rank=8,
          kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
          v_head_dim=4), "CCA beside latent attention"),
    (dict(index_heads=2, index_dim=4, index_topk=4),
     "CCA beside an indexer"),
    (dict(eva_window=16, eva_chunk=4), "CCA beside EVA windows"),
    (dict(attn_gate=True), "CCA beside an output gate"),
    (dict(hc_mult=4, hc_sinkhorn_iters=3), "CCA beside hyper-connections"),
    (dict(layer_kinds=("shortcut", "dense"), n_experts=4, top_k=2, moe_ff=8),
     "CCA beside a shortcut layer"),
    (dict(mixers=("mamba", "attention"), d_inner=8, d_state=4, d_conv=4,
          dt_rank=2), "CCA beside Mamba layers"),
    (dict(mixers=("gdn", "attention"), d_conv=4, gdn_k_heads=1,
          gdn_v_heads=2, gdn_k_dim=4, gdn_v_dim=4),
     "CCA beside Gated DeltaNet layers"),
    (dict(cca_taps=(2,)), "cca_taps"), (dict(cca_taps=(2, 1)), "cca_taps"),
    (dict(mixers=("attention", "cca")), "every layer's mixer is cca")])
def test_model_spec_refuses_cca_beside_what_it_cannot_serve(kw, match):
    with pytest.raises(ValueError, match=match):
        ModelSpec(**{**BASE, "cca_taps": (2, 2), **kw})


@pytest.mark.parametrize("kw, match", [
    (dict(router="mlp"), "router_width"),
    (dict(router_width=8), "router_width"),
    (dict(router="gelu_mlp"), "unknown router kind"),
    (dict(mixers=("cca", "cca")), "comes with cca_taps"),
    (dict(res_scale=True, layer_kinds=("shortcut", "dense"), n_experts=4,
          top_k=2, moe_ff=8), "res_scale"),
    (dict(res_scale=True, hc_mult=4, hc_sinkhorn_iters=3), "res_scale"),
    (dict(res_scale=True, mixers=("mamba", "attention"), d_inner=8,
          d_state=4, d_conv=4, dt_rank=2), "res_scale")])
def test_model_spec_refuses_a_router_or_a_scaling_it_cannot_serve(kw, match):
    with pytest.raises(ValueError, match=match):
        ModelSpec(**dict(BASE, **kw))


def test_parameter_cache_and_state_byte_counts_are_the_issues():
    """At the published widths as the cell cuts them: 207.58 M parameters a
    layer and 537.13 M in the tied table, 3,858.5 M = 7.72 GB in bf16; K and
    V rows of 2 x 128 values a token a layer, 16,384 B a token; one row of
    ``c``, one of ``a`` and the value's shifted half a lane a layer, 86,016
    B a lane."""
    spec = zaya_spec(PUBLISHED)
    tree = jax.eval_shape(partial(init_params, spec, 262272, 0))
    size = lambda t: sum(int(np.prod(x.shape))
                         for x in jax.tree_util.tree_leaves(t))
    layer = tree["layer5"]
    assert size(layer["cca"]) + size(layer["wo"]) == (
        5_242_880 + 3_840 + 328_960 + 2)
    assert size(layer["moe"]["router"]) + 17 == 661_009
    assert size(layer["moe"]["w13"]) + size(layer["moe"]["w2"]) == (
        16 * 12_582_912)
    # the key temperature and depth averaging are the mechanisms' own: a
    # scalar a KV head in every CCA layer, ``gamma`` in every router but
    # the first
    assert tree["layer0"]["cca"]["tau"].shape == (2,)
    assert "gamma" not in tree["layer0"]["moe"]["router"]
    assert layer["moe"]["router"]["gamma"].shape == (256,)
    assert size(layer) == size(tree["layer0"]) + 256 == 207_583_763
    assert "lm_head" not in tree and size(tree["embed"]) == 537_133_056
    assert round(size(tree) / 1e6, 1) == 3858.5
    assert round(2 * size(tree) / 1e9, 2) == 7.72
    pool = PagedKVPool(n_pages=3, page_size=16, n_layers=16, n_heads=2,
                       head_dim=128, dtype=jnp.bfloat16)
    assert pool.kv.shape == (16, 3, 2, 16, 256)
    assert pool.bytes_per_token == 16_384
    shapes = lane_state_shapes(spec, 32, jnp.bfloat16)
    assert [s for s, _ in shapes] == [(16, 1, 32, 1280), (16, 1, 32, 1280),
                                      (16, 1, 32, 128)]
    assert sum(int(np.prod(s)) * d.itemsize for s, d in shapes) // 32 == (
        86_016)


# ------------------------------------- the seeded draw, published widths ----

def test_the_seeded_router_spreads_its_choice_and_gamma_changes_it():
    """``zaya_leaf``'s router at the published widths, sixteen layers on
    4,096 random normed rows: at every layer every one of the 17 columns is
    chosen by at least 1 % and none by more than 25 % of the rows, and with
    ``gamma`` 0 at least a tenth of the rows choose another column."""
    d, w, e, n = 2048, 256, 17, 4096
    key = jax.random.PRNGKey(5)
    rows = jax.random.normal(jax.random.fold_in(key, 999), (n, d))
    prev = None
    for layer in range(16):
        r = {"norm": {"scale": jnp.ones((w,))}}
        shapes = {"down": (d, w), "w1": (w, w), "w2": (w, w), "w3": (w, e),
                  "gamma": (w,), "down_b": (w,), "b1": (w,), "b2": (w,)}
        for j, (name, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, 16 * layer + j)
            drawn = zaya_leaf(f"['layer{layer}']['moe']['router']['{name}']",
                              shape, k)
            r[name] = (0.02 * jax.random.normal(k, shape) if drawn is None
                       else drawn)
        if not layer:
            del r["gamma"]
        bias = 0.02 * jax.random.normal(jax.random.fold_in(key, 16 * layer
                                                           + 15), (e,))
        idx, weight, state = route(r, rows, 1, "mlp", bias, 1.0, False, prev,
                                   1e-5)
        alone, _w, _s = route(r, rows, 1, "mlp", bias, 1.0, False, None, 1e-5)
        share = np.bincount(np.asarray(idx)[:, 0], minlength=e) / n
        assert share.min() >= 0.01 and share.max() <= 0.25, (layer, share)
        assert 0.15 < float(weight.mean()) < 0.7
        if layer:
            assert (np.asarray(idx) != np.asarray(alone)).mean() >= 0.1
        prev = state


def test_the_seeded_convolutions_are_as_large_as_the_mean_they_join():
    """``zaya_leaf``'s ``w0`` and ``W1`` at the published widths: the
    convolved part of ``q`` is between a half and twice the norm of the q-k
    mean part, so a lost tail shows."""
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(1)
    c = rng.standard_normal((64, 1280)) * 0.02 * 2048 ** 0.5   # h W, h normed
    w0 = f64(zaya_leaf("['cca']['conv0_w']", (2, 1280), key))
    w1 = f64(zaya_leaf("['cca']['conv1_w']", (2, 10, 128, 128),
                       jax.random.fold_in(key, 1)))
    assert zaya_leaf("['cca']['in_proj']", (4, 4), key) is None
    a = w0[1] * c[1:] + w0[0] * c[:-1]
    conv = sum(np.einsum("tgd,gde->tge", x.reshape(-1, 10, 128), w1[j])
               for j, x in ((1, a[1:]), (0, a[:-1])))[:, :8]
    qt, kt = c[2:, :1024].reshape(-1, 2, 4, 128), c[2:, 1024:].reshape(
        -1, 2, 1, 128)
    ratio = np.linalg.norm(conv) / np.linalg.norm((qt + kt) / 2)
    assert 0.5 < ratio < 2.0
    tau = np.asarray(zaya_leaf("['cca']['tau']", (2,), key))
    assert (0.8 <= tau).all() and (tau <= 1.2).all()


# ---------------------------------------------- steps 2, 3, 4, 6 by hand ----

def _one_layer(model, **changed):
    """A one-layer model of the tiny widths without RoPE (the rows in the
    pages are then the normed keys themselves), its ``cca`` leaves
    ``changed``."""
    spec, params = model
    one = dataclasses.replace(spec, n_layers=1, layer_kinds=("moe",),
                              mixers=(), rope_theta=None, rotary_dim=0)
    layer = dict(params["layer0"])
    layer["cca"] = dict(layer["cca"], **changed)
    return one, dict(params, layer0=layer)


def _served_rows(spec, params, tokens, split):
    """``tokens`` through lane 1 in two rounds cut at ``split``: layer 0's
    ``(K rows (T, G, D), V rows (T, G, D), the three tails)``."""
    store, tables = _fresh(spec, junk=True)
    at = 0
    for part in (tokens[:split], tokens[split:]):
        if len(part):
            _last, store, _ = _round(spec, params, store, tables, {1: part},
                                     {}, [0, at, 0, 0])
            at += len(part)
    pages = np.asarray(store[0])[0, np.asarray(tables)[1]]   # (4, 2, S, row)
    rows = np.moveaxis(pages, 1, 0).reshape(2, -1, 2, spec.head_dim)
    return rows[0, :len(tokens)], rows[1, :len(tokens)], _tails(store, 1)


def _hand(spec, params, tokens):
    """Steps 1-4 and 6 of layer 0 in float64, a token, a head and a tap at a
    time: ``c``, ``a`` (depthwise), ``d`` (grouped), the q-k means, the
    normed ``q`` and ``k``, ``v``."""
    p = params["layer0"]
    cca = {k: f64(v) for k, v in p["cca"].items()}
    hq, g, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    nq, nc = hq * d, (hq + g) * d
    x = f64(params["embed"])[np.asarray(tokens)]
    h = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + spec.rms_eps) * f64(
        p["ln1"]["scale"])
    proj = h @ cca["in_proj"]
    t = len(tokens)
    c, v1, v2 = proj[:, :nc], proj[:, nc:nc + g * d // 2], proj[:, nc + g
                                                                * d // 2:]
    a, conv = np.zeros((t, nc)), np.zeros((t, hq + g, d))
    for i in range(t):
        a[i] = cca["conv0_b"] + cca["conv0_w"][1] * c[i]
        if i:
            a[i] += cca["conv0_w"][0] * c[i - 1]
    for i in range(t):
        for head in range(hq + g):
            cut = slice(head * d, (head + 1) * d)
            conv[i, head] = cca["conv1_b"][cut] + a[i, cut] @ cca["conv1_w"][
                1, head]
            if i:
                conv[i, head] += a[i - 1, cut] @ cca["conv1_w"][0, head]
    q, k = np.zeros((t, hq, d)), np.zeros((t, g, d))
    mean_q, mean_k = np.zeros((t, hq, d)), np.zeros((t, g, d))
    for i in range(t):
        for kv in range(g):
            kt = c[i, nq + kv * d:nq + (kv + 1) * d]
            mine = [c[i, j * d:(j + 1) * d]
                    for j in range(kv * hq // g, (kv + 1) * hq // g)]
            mean_k[i, kv] = (np.mean(mine, axis=0) + kt) / 2
            for j, qt in zip(range(kv * hq // g, (kv + 1) * hq // g), mine):
                mean_q[i, j] = (qt + kt) / 2
    unit = lambda z: z / np.linalg.norm(z, axis=-1, keepdims=True) * d ** 0.5
    q = unit(conv[:, :hq] + mean_q)
    k = unit(conv[:, hq:] + mean_k) * cca["tau"][None, :, None]
    shifted = np.concatenate([np.zeros((1, g * d // 2)), v2[:-1]])
    v = np.concatenate([v1, shifted], -1).reshape(t, g, d)
    return dict(c=c, a=a, conv=conv, mean_k=mean_k, q=q, k=k, v=v, v2=v2)


@pytest.mark.parametrize("step", [2, 3, 4, 6])
def test_each_step_of_the_mixer_against_a_hand_computation(model, step):
    """Layer 0's rows in the pages and its tails after nine tokens cut 4 +
    5, against float64 written out a token, a head and a tap at a time.
    Step 2: the depthwise taps (the ``a`` tail) and the grouped taps (the
    keys, which they carry half of); step 3: with the convolutions zeroed
    the key is the normed q-k mean alone; step 4: a key head's length is
    ``tau sqrt(D)``; step 6: the value's halves, the second from the token
    before, zero for the first token."""
    changed = {}
    if step == 3:
        _spec, params = model
        changed = {name: jnp.zeros_like(params["layer0"]["cca"][name])
                   for name in ("conv1_w", "conv1_b")}
    spec, params = _one_layer(model, **changed)
    tokens = np.random.default_rng(step).integers(0, VOCAB, 9)
    k_rows, v_rows, (tail_c, tail_a, tail_v) = _served_rows(spec, params,
                                                            tokens, 4)
    want = _hand(spec, params, tokens)
    tol = dict(rtol=2e-5, atol=2e-5)
    if step == 2:
        np.testing.assert_allclose(tail_c[0, 0], want["c"][-1], **tol)
        np.testing.assert_allclose(tail_a[0, 0], want["a"][-1], **tol)
        np.testing.assert_allclose(k_rows, want["k"], **tol)
        # and the convolved part is no rounding error of the key
        bare = np.linalg.norm(want["conv"][:, 8:]) / np.linalg.norm(
            want["mean_k"])
        assert bare > 0.2
    elif step == 3:
        unit = want["mean_k"] / np.linalg.norm(want["mean_k"], axis=-1,
                                               keepdims=True) * 4.0
        tau = f64(params["layer0"]["cca"]["tau"])
        np.testing.assert_allclose(k_rows, unit * tau[None, :, None], **tol)
    elif step == 4:
        tau = f64(params["layer0"]["cca"]["tau"])
        np.testing.assert_allclose(
            np.linalg.norm(k_rows, axis=-1),
            np.broadcast_to(4.0 * tau, (9, 2)), rtol=2e-5)
        assert np.abs(tau - 1).max() > 0.01
    else:
        np.testing.assert_allclose(v_rows, want["v"], **tol)
        assert (v_rows[0, 1] == 0).all() and np.abs(v_rows[1, 1]).max() > 0
        np.testing.assert_allclose(tail_v[0, 0], want["v2"][-1], **tol)


def test_the_window_is_three_uses_of_one_row_gather():
    """``_segment_window`` on a packed round of two lanes (a first chunk, a
    later chunk that reaches into its tail) and a decode row, three taps:
    every row's window by hand; ``_segment_conv`` is its weighted sum."""
    rng = np.random.default_rng(0)
    lanes, k, ch = 3, 3, 4
    prefill = {0: [1, 2, 3], 2: [4, 5]}
    toks, row_lane, row_off, q_lens = pack_round(lanes, prefill, {1: 9})
    x = jnp.asarray(rng.standard_normal((len(toks), ch)), jnp.float32)
    tails = jnp.asarray(rng.standard_normal((k - 1, lanes, ch)), jnp.float32)
    kv_lens = np.asarray([3, 7, 6], np.int32)       # lane 0 starts at 0
    m = len(toks) - lanes
    back = np.maximum(row_lane, 0) * m + row_off
    spread = np.zeros(lanes * m, np.int32)
    spread[back[row_lane >= 0]] = np.arange(len(toks))[row_lane >= 0]
    seg = dict(row_seg=(i32(row_lane), i32(row_off)), q_lens=i32(q_lens),
               kv_lens=i32(kv_lens), rows=(i32(spread),))
    window, new = _segment_window(x, k, tails, seg)
    window, new, xs = np.asarray(window), np.asarray(new), np.asarray(x)
    old = np.array(tails)
    old[:, 0] = 0                                   # a fresh segment
    for row, (lane, off) in enumerate(zip(row_lane, row_off)):
        if lane < 0:
            continue
        seq = np.concatenate([old[:, lane], xs[row - off:row + 1]])
        np.testing.assert_array_equal(window[:, row], seq[-k:])
    np.testing.assert_array_equal(new[:, 0], xs[1:3])           # 3 rows: 2
    np.testing.assert_array_equal(new[:, 1], np.stack([old[1, 1], xs[m + 1]]))
    np.testing.assert_array_equal(new[:, 2], xs[3:5])
    w = jnp.asarray(rng.standard_normal((k, ch)), jnp.float32)
    conv = jnp.broadcast_to(tails[None], (2,) + tails.shape)
    acc, conv2 = _segment_conv(x, w, conv, 1, seg)
    np.testing.assert_allclose(
        np.asarray(acc), (window * np.asarray(w)[:, None]).sum(0), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(conv2[1]), new)
    np.testing.assert_array_equal(np.asarray(conv2[0]), np.asarray(tails))


# ------------------------------------------ the router, the skip column ----

def _ffn_rows(spec, p, layer, x, routed=None):
    valid = jnp.ones(x.shape[:2], bool)
    return _ffn_block(spec, p, layer, x, valid, jnp.float32, routed=routed)


def test_depth_averaging_off_is_sixteen_independent_routers(model):
    """With ``gamma`` 0 a layer's choice and weights are those of its router
    alone, whatever state it is handed; with the seeded ``gamma`` the state
    handed on changes them.  The state a layer hands on is ``r`` AFTER its
    averaging."""
    spec, params = model
    p = params["layer2"]
    r = p["moe"]["router"]
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    prev = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    alone = route(r, h, 1, "mlp", p["moe"]["bias"], 1.0, False, None, 1e-5)
    off = dict(r, gamma=jnp.zeros_like(r["gamma"]))
    same = route(off, h, 1, "mlp", p["moe"]["bias"], 1.0, False, prev, 1e-5)
    for a, b in zip(alone, same):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got = route(r, h, 1, "mlp", p["moe"]["bias"], 1.0, False, prev, 1e-5)
    assert (np.asarray(got[0]) != np.asarray(alone[0])).mean() > 0.1
    np.testing.assert_allclose(
        np.asarray(got[2]),
        np.asarray(alone[2]) + np.asarray(r["gamma"]) * np.asarray(prev),
        rtol=1e-5, atol=1e-6)
    # layer 0 has no gamma: a state handed to it by mistake changes nothing
    r0 = params["layer0"]["moe"]["router"]
    assert "gamma" not in r0
    a0 = route(r0, h, 1, "mlp", p["moe"]["bias"], 1.0, False, None, 1e-5)
    b0 = route(r0, h, 1, "mlp", p["moe"]["bias"], 1.0, False, prev, 1e-5)
    np.testing.assert_array_equal(np.asarray(a0[2]), np.asarray(b0[2]))


def test_the_router_by_hand_and_the_bias_chooses_without_weighing(model):
    spec, params = model
    p = params["layer1"]["moe"]
    r = {k: f64(v) for k, v in p["router"].items() if k != "norm"}
    rng = np.random.default_rng(2)
    h = rng.standard_normal((40, 64)).astype(np.float32)
    prev = rng.standard_normal((40, 16)).astype(np.float32)
    bias = np.zeros(17, np.float32)
    bias[5] = 0.5                                   # column 5 takes rows
    idx, w, state = route(p["router"], jnp.asarray(h), 1, "mlp",
                          jnp.asarray(bias), 1.0, False, jnp.asarray(prev),
                          1e-5)
    from math import erf
    gelu = np.vectorize(lambda v: 0.5 * v * (1 + erf(v / 2 ** 0.5)))
    s = f64(h) @ r["down"] + r["down_b"] + r["gamma"] * f64(prev)
    u = s / np.sqrt((s ** 2).mean(-1, keepdims=True) + 1e-5) * f64(
        p["router"]["norm"]["scale"])
    z = gelu(gelu(u @ r["w1"] + r["b1"]) @ r["w2"] + r["b2"]) @ r["w3"]
    prob = np.exp(z - z.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(state), s, rtol=1e-5, atol=1e-5)
    want = (prob + bias).argmax(-1)
    np.testing.assert_array_equal(np.asarray(idx)[:, 0], want)
    np.testing.assert_allclose(np.asarray(w)[:, 0],
                               prob[np.arange(40), want], rtol=1e-4)
    assert (want == 5).sum() > (prob.argmax(-1) == 5).sum()


def test_the_skip_column_gives_its_probability_times_the_normed_input(model):
    """A selection bias that sends every row to the 17th column: the expert
    sublayer's output is ``p_e * h`` and no expert product is counted."""
    spec, params = model
    plain = dataclasses.replace(spec, res_scale=False)
    p = dict(params["layer0"])
    bias = jnp.zeros((17,)).at[16].set(10.0)
    p["moe"] = dict(p["moe"], bias=bias)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 6, 64)),
                    jnp.float32)
    (out, state), stats = _ffn_rows(plain, p, 0, x)
    h = f64(x) / np.sqrt((f64(x) ** 2).mean(-1, keepdims=True) + 1e-5) * f64(
        p["ln2"]["scale"])
    _idx, w, _state = route(p["moe"]["router"], jnp.asarray(h[0], jnp.float32),
                            1, "mlp", bias, 1.0, False, None, 1e-5)
    np.testing.assert_allclose(np.asarray(out), f64(x) + f64(w)[None] * h,
                               rtol=2e-5, atol=2e-5)
    stats = np.asarray(stats)
    assert stats[16] == 6 and stats[:16].sum() == 0 and stats[17] == 0
    assert state.shape == (6, 16)


def test_unit_scaling_and_zero_biases_give_the_plain_residuals_logits(
        model, reference):
    """``s_r = s_o = 1`` and ``b_r = b_o = 0``: the logits of the same
    weights under the plain residual ``x + f(norm(x))``; the seeded vectors
    are not that."""
    spec, params = model
    unit = dict(params)
    for i in range(spec.n_layers):
        p = dict(params[f"layer{i}"])
        for name in ("res_attn", "res_ffn"):
            p[name] = {k: (jnp.ones_like(v) if k.startswith("s_")
                           else jnp.zeros_like(v))
                       for k, v in p[name].items()}
        unit[f"layer{i}"] = p
    plain = dataclasses.replace(spec, res_scale=False)
    tokens = np.random.default_rng(4).integers(0, VOCAB, 13)
    outs = []
    for sp, ps in ((spec, unit), (plain, params), (spec, params)):
        store, tables = _fresh(sp)
        last, *_ = _round(sp, ps, store, tables, {0: tokens}, {},
                          [0, 0, 0, 0])
        outs.append(last[0])
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)
    assert np.abs(outs[2] - outs[1]).max() > 1e-2
    np.testing.assert_allclose(
        outs[0], reference.last_logits(unit, tokens.tolist(), 1,
                                       **reference.hyper_of(CONFIG))[0],
        rtol=1e-4, atol=1e-4)
    x, y = jnp.ones((2, 3)), jnp.full((2, 3), 2.0)
    four = {"res_ffn": {"s_r": jnp.full((3,), 2.0), "b_r": jnp.ones((3,)),
                        "s_o": jnp.full((3,), 0.5),
                        "b_o": jnp.full((3,), 4.0)}}
    assert _residual(plain, four, "res_ffn", x, y).tolist() == [[3.0] * 3] * 2
    assert _residual(spec, four, "res_ffn", x, y).tolist() == [
        [2 * 2 + 0.5 * 6.0] * 3] * 2


# ----------------------------------------------- the steps, the reference ----

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
@pytest.mark.parametrize("offset", [0, 1, 2, 3])
def test_a_chunk_boundary_at_every_offset_of_a_prompt(model, reference,
                                                      use_kernel, offset):
    """A 14-token prompt through packed rounds cut after ``offset`` tokens
    (0: one chunk) and again five tokens later, over a store full of junk:
    the reference's logits at the last position, and the tails a lane would
    keep.  The second chunk starts where one, two or three tokens lie
    behind it: the grouped taps' tail then rests on a depthwise tap that
    reached the zero start, its own tail, or a row of the round."""
    spec, params = model
    tokens = np.random.default_rng(6).integers(0, VOCAB, 14)
    hyper = reference.hyper_of(CONFIG)
    want, held = reference.last_logits(params, tokens.tolist(), 1,
                                       stores=True, **hyper)
    store, tables = _fresh(spec, junk=True)
    at = 0
    for n in (offset, 5, 14 - 5 - offset):
        if n:
            last, store, _ = _round(spec, params, store, tables,
                                    {2: tokens[at:at + n]}, {},
                                    [0, 0, at, 0], use_kernel)
            at += n
    np.testing.assert_allclose(last[2], want[0], rtol=1e-4, atol=1e-4)
    # every layer's three tails, one row a layer
    np.testing.assert_allclose(
        np.concatenate([t[:, -1] for t in _tails(store, 2)], axis=-1),
        held["state"], rtol=1e-4, atol=1e-5)
    # the lanes that ran nothing still hold what they held
    assert all((t == 3).all() for t in _tails(store, 0) + _tails(store, 3))


def test_zeroed_tails_at_a_chunk_boundary_fail(model, reference):
    """The fault this model adds, made by hand: the lane's tails zeroed
    between two chunks.  The logits leave the reference's."""
    spec, params = model
    tokens = np.random.default_rng(6).integers(0, VOCAB, 14)
    want = reference.last_logits(params, tokens.tolist(), 1,
                                 **reference.hyper_of(CONFIG))[0]
    store, tables = _fresh(spec)
    _last, store, _ = _round(spec, params, store, tables, {2: tokens[:6]},
                             {}, [0, 0, 0, 0])
    zeroed = (store[0], tuple(jnp.zeros_like(t) for t in store[1]))
    for kept, wrong in ((store, False), (zeroed, True)):
        last, *_ = _round(spec, params, kept, tables, {2: tokens[6:]}, {},
                          [0, 0, 6, 0])
        assert (np.abs(last[2] - want).max() > 1e-2) == wrong


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_decode_rows_inside_a_round_then_a_block_of_two(model, reference,
                                                        use_kernel):
    """Lane 0 holds 11 positions and rides the round with its decode row,
    lane 2 brings a first chunk of 10, lane 3 a later chunk; then
    ``paged_decode_block`` at K = 2 from the round's carry.  Every pick's
    logits are the reference's: the decode row read its tails from the
    slot, the block's second step from the first's."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    rng = np.random.default_rng(8)
    # (lengths two apart: the reference is compiled once a length)
    a, c, d = (rng.integers(0, VOCAB, n) for n in (12, 10, 8))
    store, tables = _fresh(spec, junk=True)
    _last, store, _ = _round(spec, params, store, tables,
                             {0: a[:11], 3: d[:4]}, {}, [0, 0, 0, 0],
                             use_kernel)
    last, carry, store, _moe = _round(
        spec, params, store, tables, {2: c, 3: d[4:]}, {0: int(a[11])},
        [11, 0, 0, 4], use_kernel, rem=[5, 0, 5, 5], with_carry=True)
    for lane, seq in ((0, a), (2, c), (3, d)):
        np.testing.assert_allclose(
            last[lane], reference.last_logits(params, seq.tolist(), 1,
                                              **hyper)[0],
            rtol=1e-4, atol=1e-4)
    assert np.asarray(carry[2]).tolist() == [True, False, True, True]
    first = np.asarray(carry[1])
    toks, lps, emitted, _carry, store, _ = decode_block(
        _jitted(paged_decode_block, spec, use_kernel, lanes=LANES,
                max_pages=4, k=2), params, store, tables, carry, 2,
        fresh=False, spec=spec)
    assert emitted[[0, 2, 3]].all() and not emitted[1].any()
    for lane, seq in ((0, a), (2, c), (3, d)):
        stream = [int(first[lane])] + toks[lane].tolist()
        want = reference.last_logits(params, seq.tolist() + stream[:2], 2,
                                     **hyper).astype(np.float64)
        logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
        np.testing.assert_allclose(lps[lane], logp[np.arange(2), stream[1:]],
                                   rtol=1e-4, atol=1e-4)
        assert stream[1:] == want.argmax(-1).tolist()


def test_token_by_token_decode_steps_are_the_one_chunk(model, reference):
    spec, params = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, 9)
    store, tables = _fresh(spec, junk=True)
    step = _jitted(paged_decode_step, spec, False)
    for pos, tok in enumerate(tokens):
        logits, store, _ = step(
            params, store, tables, i32([0, pos, 0, 0]), i32([0, tok, 0, 0]),
            jnp.asarray([False, True, False, False]))
    np.testing.assert_allclose(
        np.asarray(logits)[1],
        reference.last_logits(params, tokens.tolist(), 1,
                              **reference.hyper_of(CONFIG))[0],
        rtol=1e-4, atol=1e-4)
    assert all((t == 3).all() for t in _tails(store, 0))


def test_the_padded_form_refuses_cca_layers(model):
    spec, params = model
    store, tables = _fresh(spec)
    with pytest.raises(NotImplementedError, match="padded"):
        paged_ragged_forward(params, store, tables,
                             jnp.zeros((LANES, 3), jnp.int32),
                             i32([3] * LANES), i32([3] * LANES), **_kw(spec))


# ------------------------------------------------ through the scheduler ----

def _engine(spec, params, **kw):
    kw = dict(dict(lanes=2, max_len=128, page_size=PAGE,
                   compute_dtype=jnp.float32, prefill_chunk=8), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             **kw)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_scheduler_rounds_blocks_and_readmission_against_the_reference(
        model, reference, use_kernel):
    """Five requests on two lanes: prompts that take several mixed rounds of
    8 (decode rows riding them), decode blocks, and three re-admissions into
    lanes, slots and pages another request left; every emitted token's
    log-probability is the reference's, and what the last stream leaves in
    both stores is the reference's."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    cb = _engine(spec, params, use_kernel=use_kernel)
    try:
        assert cb.use_kernel == use_kernel
        assert cb.pool.entry_kind == "kv" and cb.pool.n_layers == 3
        assert cb.state.kind == "cca" and len(cb.state.arrays) == 3
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n).tolist()
                   for n in (21, 37, 6, 18, 22)]
        futures = [cb.submit(p, 9, logprobs=True) for p in prompts]
        for prompt, fut in zip(prompts, futures):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(params, prompt, tokens, logprobs, **hyper)
            assert got["logprob_err_max"] < 2e-4 and got["argmax_gap"] < 2e-4
        state = cb.debug_state()
        d = state["dispatch"]
        assert d["kinds"]["mixed"] >= 5 and d["kinds"]["decode"] > 0
        assert d["mixed_decode_rows"] > 0
        assert state["state"]["kind"] == "cca"
        assert state["state"]["zero_starts"] == 5
        assert state["state"]["rule"] == {"decode": "xla", "round": "xla"}
        assert state["state"]["bytes_per_lane"] == 3 * (160 + 160 + 16) * 4
        assert state["cca"] == {
            "taps": [2, 2],
            "state_bytes_per_lane": state["state"]["bytes_per_lane"],
            "kv_bytes_per_token": 3 * 2 * 32 * 4,
            "rows": {"round": d["lane_work"]["round"]["rows"],
                     "decode": d["lane_work"]["decode"]["rows"]}}
        assert sum(state["cca"]["rows"].values()) == sum(
            map(len, prompts)) + 5 * 8
        moe = state["moe"]
        assert (moe["zero_columns"], moe["zero_first"], moe["held"]) == (
            1, 16, 16)
        per_layer = np.asarray(moe["assignments"])
        assert per_layer.shape == (3, 17)
        assert (per_layer.sum(1) == sum(map(len, prompts)) + 5 * 8).all()
    finally:
        cb.shutdown()


def test_what_a_finished_stream_leaves_in_the_stores_is_the_references(
        model, reference):
    """``debug_state()["last_release"]`` names the lane and the pages of the
    request that ended last: every layer's K/V rows and three tails are the
    reference's after every token but the last emitted, which is what the
    benchmark's ``correct`` reads; the second, SHORTER request reuses the
    lane, and no value of the first one's tails survives."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    cb = _engine(spec, params, lanes=1)
    try:
        rng = np.random.default_rng(4)
        for n, steps in ((21, 10), (5, 4)):
            prompt = rng.integers(0, VOCAB, n).tolist()
            toks, lps = cb.submit(prompt, steps=steps, logprobs=True).result(
                timeout=300)
            held = cb.debug_state()["last_release"]
            assert held["length"] == n + steps - 1 and held["lane"] == 0
            state = np.concatenate([np.asarray(t[:, -1, 0])
                                    for t in cb.state.arrays], axis=-1)
            kv = np.asarray(cb.pool.kv[:, np.asarray(held["pages"])])
            kv = np.moveaxis(kv, 2, 1).reshape(3, 2, -1, kv.shape[-1])
            got = reference.token_errors(
                params, prompt, toks, lps,
                stores=(state, kv[:, :, :held["length"]]),
                seams=range(8, n, 8), **hyper)       # chunks of 8
            assert got["state_err"] < 1e-5 and got["kv_err"] < 1e-5
            assert got["kv_row_max"] < 1e-4
            # ... and of every layer: float32 flips no expert
            assert got["layer_state_err"].shape == (3,)
            assert got["layer_state_err"].max() < 1e-4
            assert got["layer_kv_err"].max() < 1e-4
            assert got["seam_rows"].shape == (3, (n - 1) // 8)
            assert got["seam_rows"].max(initial=0) < 1e-4
            assert got["logprob_err"].max() < 2e-4
            # the tails follow the LAST token taken in: after the prompt
            # alone they were others (a greedy stream repeats its tokens,
            # so one token fewer need not be)
            _logits, want = reference.last_logits(params, prompt, 1,
                                                  stores=True, **hyper)
            assert np.linalg.norm(state - want["state"]) > 1e-2 * (
                np.linalg.norm(state))
        assert cb.debug_state()["state"]["zero_starts"] == 2
    finally:
        cb.shutdown()


def _fresh_tokens(spec, params, prompt, steps):
    cb = _engine(spec, params, lanes=1)
    try:
        return cb.submit(prompt, steps).result(timeout=300)
    finally:
        cb.shutdown()


def test_a_preempted_request_prefills_again_to_a_fresh_engines_tokens(model):
    """A high-priority arrival evicts the one lane's request mid-decode; the
    victim prefills again from position 0 (prompt + what it emitted) into a
    slot the other request used meanwhile, and ends with the tokens of an
    undisturbed run."""
    spec, params = model
    rng = np.random.default_rng(10)
    p_low, p_hi = (rng.integers(0, VOCAB, n).tolist() for n in (10, 6))
    cb = _engine(spec, params, lanes=1)
    try:
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 14, on_token=started)
        assert started.wait(timeout=120)
        f_hi = cb.submit(p_hi, 5, priority=10)
        started.release()
        got_hi, got_low = f_hi.result(timeout=300), f_low.result(timeout=300)
        assert cb.preemptions >= 1
        assert cb.debug_state()["state"]["zero_starts"] >= 3
    finally:
        cb.shutdown()
    assert got_low == _fresh_tokens(spec, params, p_low, 14)
    assert got_hi == _fresh_tokens(spec, params, p_hi, 5)


@pytest.mark.parametrize("option", [
    dict(prefix_cache=True), dict(draft_params={"layer0": {}}),
    dict(mesh=object()), dict(kv_offload=True), dict(kv_publish=True),
    dict(kv_dtype=jnp.bfloat16)],
    ids=["prefix_cache", "draft_params", "mesh", "kv_offload", "kv_publish",
         "kv_dtype"])
def test_options_the_lane_state_does_not_carry_are_refused_by_name(
        model, option, request):
    spec, params = model
    name = request.node.callspec.id
    with pytest.raises(NotImplementedError, match=name) as err:
        _engine(spec, params, **option)
    assert "cca layers" in str(err.value) and "moe FFNs" in str(err.value)
    plan = test_engine_plan._plan(spec)
    assert plan.state_kind == "cca" and plan.pool_layers == 3
    assert plan.state_rule == {"decode": "xla", "round": "xla"}


# ------------------------------------------------ this kind's scopes ----

def test_this_kinds_programs_hold_its_scopes(model):
    from test_step_programs import _lowered
    spec, _params = model
    _texts, scoped = _lowered(spec, VOCAB, 0, dict(lanes=2, max_len=64,
                                                   page_size=8))
    for scope in ("cca_proj", "cca_mix", "cca_out", "res_scale",
                  "moe_router", "moe_zero"):
        assert scope in scoped
