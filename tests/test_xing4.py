"""Xing4.0 through the paged engine: manifold-constrained hyper-connections
(four residual streams, mixed a token a sublayer by a Sinkhorn-projected
matrix) around latent attention and routed experts, RoPE under YaRN.

Tiny widths that keep every ratio of ``xing4_0`` (a low-rank query, a latent
narrower than the heads it expands to, nope != v != rope, one leading dense
layer, 8 sigmoid-routed experts at top-3 beside a shared one, four streams
and twenty sweeps, a YaRN ramp that leaves one pair alone and divides three),
held to the benchmark's plain float32 reference (``perf/reference/
xing4_0.py``: expanded attention, a loop over experts, the streams by
einsum, nothing imported from the program).  The reference reads the
PUBLISHED ``q_b_proj``, the engine the one with YaRN's softmax factor folded
in (:func:`scale_queries`).
"""

import dataclasses
import importlib.util
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_engine_plan
from tpulab.engine import paged_steps
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_mhc_post, _mhc_pre, pack_round,
                                       pack_words, paged_decode_block,
                                       paged_decode_step, paged_mixed_step,
                                       paged_ragged_forward, dispatch_fields,
                                       unpack_words, result_fields,
                                       moe_shape, ROUND_STOPS)
from tpulab.models.spec import (ModelSpec, init_params, mla_scales,
                                scale_queries, xing4_spec)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, D_FF = 97, 96
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
CONFIG = {
    "model_type": "xing4_0", "hidden_size": 64, "intermediate_size": D_FF,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 12, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "moe_layer_freq": 1, "ep_size": 1, "attention_bias": False,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "vocab_size": VOCAB,
}
#: the published configuration as the benchmark cuts it (ISSUE 50)
PUBLISHED = dict(
    CONFIG, hidden_size=3584, intermediate_size=9216,
    moe_intermediate_size=1024, num_hidden_layers=6, num_attention_heads=32,
    kv_lora_rank=512, q_lora_rank=768, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, n_routed_experts=64, num_experts_per_tok=4,
    vocab_size=131072,
    rope_scaling=dict(YARN, original_max_position_embeddings=4096))


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "xing4_0.py")
    spec = importlib.util.spec_from_file_location("ref_xing4_0", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    """``(spec, published, served)``: ``init_params``' tree read as the
    published one, and what the engine is handed."""
    spec = xing4_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    published = init_params(spec, VOCAB, D_FF, seed=3, scale=0.1)
    return spec, published, scale_queries(published, spec,
                                          mla_scales(CONFIG)[0])


def _forward(spec, params, tokens, use_kernel, chunk=None):
    """``tokens`` through ``paged_ragged_forward`` (the padded form) in
    chunks against a fresh latent pool: logits at every position, one lane
    of two used."""
    pool = PagedKVPool(n_pages=9, page_size=8, n_layers=spec.n_layers,
                       n_heads=0, head_dim=0, dtype=jnp.float32,
                       latent_width=spec.latent_width)
    kv, tables = pool.kv, jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    chunk = chunk or len(tokens)
    rows = []
    for s in range(0, len(tokens), chunk):
        part = list(tokens[s:s + chunk])
        seq = np.zeros((2, chunk), np.int32)
        seq[0, :len(part)] = part
        logits, kv, _stats = paged_ragged_forward(
            params, kv, tables, jnp.asarray(seq),
            jnp.asarray([len(part), 0], jnp.int32),
            jnp.asarray([s + len(part), 0], jnp.int32), n_heads=spec.n_heads,
            n_layers=spec.n_layers, compute_dtype=jnp.float32,
            use_kernel=use_kernel, spec=spec)
        rows.append(np.asarray(logits)[0, :len(part)])
    return np.concatenate(rows), kv


# ------------------------------------------------------------- the spec ----

def test_spec_reads_the_published_keys():
    spec = xing4_spec(CONFIG)
    assert spec.n_layers == 3 and spec.cache_entry == "latent"
    assert spec.layer_kinds == ("dense", "moe", "moe")
    assert (spec.hc_mult, spec.hc_sinkhorn_iters, spec.hc_eps,
            spec.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert spec.rope_scaling == (64.0, 16.0, 32.0, 1.0)
    assert (spec.router, spec.n_shared, spec.routed_scale, spec.norm_topk,
            spec.top_k, spec.n_experts) == ("sigmoid_bias", 1, 2.0, True, 3,
                                            8)
    assert (spec.latent_width, spec.qk_head_dim) == (40, 20)
    assert moe_shape(spec) == (2, 8 + 2)
    hash(spec)     # it keys the jit memo
    plain = dataclasses.replace(spec, hc_mult=0, hc_sinkhorn_iters=0,
                                rope_scaling=())
    assert plain.rope_inv_freq() is None


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("attention_bias", True),
    ("rope_scaling", dict(YARN, type="linear")), ("rope_scaling", None),
    ("rope_scaling", dict(YARN, mscale=0.5)), ("moe_layer_freq", 2),
    ("ep_size", 4), ("hc_mult", 1)])
def test_spec_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        xing4_spec(dict(CONFIG, **{key: value}))


@pytest.mark.parametrize("kw, match", [
    (dict(hc_mult=4), "hc_sinkhorn_iters"),
    (dict(hc_mult=1, hc_sinkhorn_iters=3), "hc_mult"),
    (dict(hc_mult=4, hc_sinkhorn_iters=3, layer_kinds=("shortcut", "dense"),
          n_experts=4, top_k=2, moe_ff=8), "no shortcut layer"),
    (dict(hc_mult=4, hc_sinkhorn_iters=3, mixers=("mamba", "attention"),
          d_inner=8, d_state=4, d_conv=4, dt_rank=2), "no lane-state mixer"),
    (dict(rope_scaling=(64.0, 4096.0, 32.0, 1.0)), "latent attention"),
    (dict(rope_scaling=(64.0, 4096.0), attention="mla"), "rope_scaling is")])
def test_model_spec_refuses_streams_or_a_table_it_cannot_serve(kw, match):
    base = dict(n_layers=2, d_model=16, n_heads=2, n_kv_heads=2, head_dim=8)
    with pytest.raises(ValueError, match=match):
        ModelSpec(**dict(base, **kw))


def test_yarn_table_and_softmax_factor_at_the_published_keys(reference):
    """By hand at ``d`` 64, theta 10000, factor 64, 4,096 original
    positions, beta 32 / 1: the ramp runs from pair 10 to pair 23; pairs
    below keep ``theta^(-2j/d)``, pairs above turn 64 times slower; the
    softmax scale carries ``(0.1 ln 64 + 1)^2`` = 2.0047."""
    spec = xing4_spec(PUBLISHED)
    assert reference.yarn_bounds(64, 10000.0, 4096.0, 32.0, 1.0) == (10, 23)
    corr = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (
        2 * math.log(10000))
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (10, 23)
    got = spec.rope_inv_freq()
    assert got.dtype == np.float32 and got.shape == (32,)
    base = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 64, rtol=1e-6)
    j = 17
    ramp = (j - 10) / 13
    assert got[j] == pytest.approx(base[j] * ((1 - ramp) + ramp / 64),
                                   rel=1e-6)
    np.testing.assert_allclose(
        got, reference.yarn_inv_freq(64, 10000.0, 64.0, 4096.0, 32.0, 1.0),
        rtol=1e-6)
    q_scale, kv_scale = mla_scales(PUBLISHED)
    assert kv_scale == 1.0
    assert q_scale == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    assert round(q_scale, 4) == 2.0047
    assert reference.yarn_mscale(64.0, 1.0) ** 2 == pytest.approx(q_scale)
    # no YaRN, no factor: the other latent configurations' scales stand
    assert mla_scales(dict(PUBLISHED, rope_scaling=None)) == (1.0, 1.0)


def test_apply_rope_takes_the_table_and_theta_alone_is_unchanged():
    from tpulab.models.transformer import apply_rope
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 5, 2, 8)), jnp.float32)
    pos = jnp.arange(5)[None] + 20
    plain = np.asarray(apply_rope(x, pos, 10000.0))
    table = 10000.0 ** (-np.arange(4) / 4)
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, pos, 10000.0, table.astype(np.float32))),
        plain, rtol=1e-5, atol=1e-6)
    slow = np.asarray(apply_rope(x, pos, 10000.0,
                                 (table / 64).astype(np.float32)))
    assert np.abs(slow - plain).max() > 0.1
    ang = 22 * table[1] / 64
    x22 = np.asarray(x)[0, 2, 1]
    assert slow[0, 2, 1, 1] == pytest.approx(
        x22[1] * math.cos(ang) - x22[5] * math.sin(ang), rel=1e-5)


def test_parameter_and_cache_byte_counts_are_the_issues():
    """At the published widths as the cell cuts them: 4,792.8 M parameters
    in the matrices and the hyper-connections (0.36 M a sublayer; ISSUE
    50's table adds its rounded parts to 4,792.7), 9.59 GB in bf16; one
    latent row of 576 values a token a layer: 6,912 B in bf16, 7,680 B in
    the pool, which pads a row to whole 128-lane tiles (640)."""
    spec = xing4_spec(PUBLISHED)
    tree = jax.eval_shape(partial(init_params, spec, 131072, 9216))
    size = lambda t: sum(int(np.prod(x.shape))
                         for x in jax.tree_util.tree_leaves(t))
    counted = sum(size(x) for x in jax.tree_util.tree_leaves(tree)
                  if len(x.shape) > 1)
    for i in range(6):
        for name in ("hc_attn", "hc_ffn"):
            counted += size(tree[f"layer{i}"][name]) - size(
                tree[f"layer{i}"][name]["phi"])
    assert counted == 939_524_096 + 128_217_142 + 5 * 745_009_206
    assert round(counted / 1e6, 1) == 4792.8
    assert round(2 * counted / 1e9, 2) == 9.59
    hc = tree["layer3"]["hc_ffn"]
    assert hc["phi"].shape == (4 * 3584, 24) and hc["bias"].shape == (24,)
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        hc)) == 14336 * 24 + 14336 + 27
    assert set(tree["layer0"]) >= {"hc_attn", "hc_ffn", "w1"}
    assert "moe" in tree["layer1"] and "moe" not in tree["layer0"]
    assert spec.n_layers * spec.latent_width * 2 == 6912
    pool = PagedKVPool(n_pages=3, page_size=16, n_layers=spec.n_layers,
                       n_heads=0, head_dim=0, dtype=jnp.bfloat16,
                       latent_width=spec.latent_width)
    assert pool.kv.shape == (6, 3, 1, 16, 640)
    assert pool.bytes_per_token == 7680


# ------------------------------------------------- the streams, by hand ----

def _maps(spec, hc, x):
    u, h_res, h_post = _mhc_pre(spec, hc, x)
    return np.asarray(u), np.asarray(h_res), np.asarray(h_post)


def test_h_res_is_doubly_stochastic_after_twenty_sweeps(model):
    spec, _published, served = model
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 9, 4, 64)),
                    jnp.float32)
    _u, h_res, h_post = _maps(spec, served["layer1"]["hc_attn"], x)
    assert h_res.shape == (2, 9, 4, 4) and h_post.shape == (2, 9, 4)
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-4)
    assert (h_res > 0).all() and (0 < h_post).all() and (h_post < 2).all()


@pytest.mark.parametrize("sign", [1.0, -1.0], ids=["plus-30", "minus-30"])
def test_entries_clamped_at_thirty_stay_finite(model, sign):
    """A bias far past the clamp on one entry: ``exp`` sees +-30 and the
    sweeps stay finite; the columns, swept last, sum to 1 (the rows of a
    matrix this lopsided are still 4 % off after twenty sweeps)."""
    spec, _published, served = model
    hc = dict(served["layer0"]["hc_ffn"])
    hc["bias"] = hc["bias"].at[2 * 4 + 5].set(sign * 1e4)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 5, 4, 64)),
                    jnp.float32)
    u, h_res, _post = _maps(spec, hc, x)
    assert np.isfinite(h_res).all() and np.isfinite(u).all()
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-4)
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=0.05)
    assert (h_res >= 0).all() and (h_res <= 1).all()
    entry = h_res[..., 1, 1]
    assert (entry > 0.95).all() if sign > 0 else (entry < 1e-6).all()


def test_seeded_maps_differ_between_tokens_and_are_no_permutation():
    """``init_params``' hyper-connections at the published stream width:
    ``H_res`` of two tokens differ by more than 0.05 and none of its entries
    passes 0.95, so neither the uniform matrix nor a permutation stands in
    for it."""
    spec = xing4_spec(dict(PUBLISHED, num_hidden_layers=1,
                           first_k_dense_replace=1))
    from tpulab.models.spec import init_hyper_connection
    hc = init_hyper_connection(jax.random.PRNGKey(7), spec)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 16, 4, 3584)) * 0.02, jnp.float32)
    _u, h_res, h_post = _maps(spec, hc, x)
    assert h_res.max() < 0.95
    spread = np.abs(h_res[0, :, None] - h_res[0, None]).max((-1, -2))
    assert spread[np.triu_indices(16, 1)].min() > 0.05
    assert np.abs(h_res - 0.25).max() > 0.2          # not uniform either
    assert np.ptp(h_post[0], axis=0).min() > 0.05


def test_one_sublayer_against_the_equations_written_out(model, reference):
    """The read and the write side on three tokens against float64 numpy."""
    spec, _published, served = model
    hc = served["layer2"]["hc_ffn"]
    rng = np.random.default_rng(4)
    x = rng.standard_normal((1, 3, 4, 64)).astype(np.float32)
    f = rng.standard_normal((1, 3, 64)).astype(np.float32)
    u, h_res, h_post = _mhc_pre(spec, hc, jnp.asarray(x))
    got = np.asarray(_mhc_post(jnp.asarray(x), h_res, h_post, jnp.asarray(f)))
    f64 = lambda a: np.asarray(a, np.float64)
    sig = lambda v: 1 / (1 + np.exp(-v))
    for t in range(3):
        X = f64(x[0, t])
        flat = X.reshape(-1)
        v = flat / np.sqrt((flat ** 2).mean() + 1e-6) * f64(
            hc["norm"]["scale"])
        proj = v @ f64(hc["phi"])
        a, b = f64(hc["alpha"]), f64(hc["bias"])
        pre = sig(a[0] * proj[:4] + b[:4])
        post = 2 * sig(a[1] * proj[4:8] + b[4:8])
        M = np.exp(np.clip(a[2] * proj[8:] + b[8:], -30, 30)).reshape(4, 4)
        for _ in range(20):
            M = M / (M.sum(1, keepdims=True) + 1e-6)
            M = M / (M.sum(0, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(u)[0, t], pre @ X, rtol=2e-5,
                                   atol=2e-6)
        np.testing.assert_allclose(got[0, t], M @ X + np.outer(post, f[0, t]),
                                   rtol=2e-5, atol=2e-5)
    ref_u, ref_m, ref_post = reference.hyper_connection(
        jnp.asarray(x[0]), hc, iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    np.testing.assert_allclose(np.asarray(u)[0], ref_u, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h_res)[0], ref_m, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(h_post)[0], ref_post, rtol=2e-5)


def _identity_maps(params, spec):
    """``params`` with every hyper-connection forced to ``h_pre = 1/4``,
    ``h_post = 1``, ``H_res = I``: no projection, the biases alone."""
    n = spec.hc_mult
    bias = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
        (60.0 * jnp.eye(n) - 30.0).reshape(-1)]).astype(jnp.float32)
    out = dict(params)
    for i in range(spec.n_layers):
        p = dict(params[f"layer{i}"])
        for name in ("hc_attn", "hc_ffn"):
            p[name] = dict(p[name], phi=jnp.zeros_like(p[name]["phi"]),
                           bias=bias)
        out[f"layer{i}"] = p
    return out


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_identity_maps_give_the_plain_residuals_logits(model, use_kernel):
    """With ``h_pre = 1/4``, ``h_post = 1`` and ``H_res = I`` the four
    streams stay equal, every sublayer reads ``x`` and writes ``x + f``: the
    logits are those of the same weights under the plain residual (the
    ``glm4_moe_lite`` path, YaRN kept), which ties the new path to the
    old."""
    spec, _published, served = model
    forced = _identity_maps(served, spec)
    plain = dataclasses.replace(spec, hc_mult=0, hc_sinkhorn_iters=0)
    tokens = np.random.default_rng(5).integers(0, VOCAB, 19).tolist()
    got, _ = _forward(spec, forced, tokens, use_kernel, chunk=8)
    want, _ = _forward(plain, served, tokens, use_kernel, chunk=8)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and the seeded maps are NOT that: the streams matter
    seeded, _ = _forward(spec, served, tokens, use_kernel, chunk=8)
    assert np.abs(seeded - want).max() > 1e-2
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 2, 64)),
                    jnp.float32)
    streams = paged_steps._streams(spec, x)
    assert streams.shape == (1, 2, 4, 64)
    u, h_res, h_post = _mhc_pre(spec, forced["layer1"]["hc_ffn"], streams)
    np.testing.assert_allclose(np.asarray(u), np.asarray(x), rtol=1e-5)
    out = np.asarray(_mhc_post(streams, h_res, h_post, 2 * x))
    for i in range(4):
        np.testing.assert_allclose(out[..., i, :], 3 * np.asarray(x),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(paged_steps._streams(spec, streams, out=True)),
        4 * np.asarray(x), rtol=1e-6)


# ------------------------------------------------- the step programs ----

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_chunked_prefill_then_decode_steps_match_the_full_forward(
        model, reference, use_kernel):
    """Three chunks through the padded form (the K+1 verify's), then
    single-token decode steps through the latent pages, against ONE full
    forward of the reference on the published ``q_b_proj``."""
    spec, published, served = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, 26).tolist()
    want = reference.last_logits(published, tokens, len(tokens),
                                 **reference.hyper_of(CONFIG))
    got, kv = _forward(spec, served, tokens[:21], use_kernel, chunk=8)
    np.testing.assert_allclose(got, want[:21], rtol=5e-5, atol=5e-5)
    tables = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    for pos in range(21, 26):
        logits, kv, st = paged_decode_step(
            served, kv, tables, jnp.asarray([pos, 0], jnp.int32),
            jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([True, False]), n_heads=spec.n_heads,
            n_layers=spec.n_layers, compute_dtype=jnp.float32,
            use_kernel=use_kernel, spec=spec)
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos],
                                   rtol=5e-5, atol=5e-5)
        assert (np.asarray(st)[:, :8].sum(1) == 3).all()


def test_the_softmax_factor_left_out_at_load_shows_in_the_logits(model,
                                                                 reference):
    spec, published, _served = model
    tokens = np.random.default_rng(1).integers(0, VOCAB, 13).tolist()
    got, _ = _forward(spec, published, tokens, use_kernel=False)
    want = reference.last_logits(published, tokens, len(tokens),
                                 **reference.hyper_of(CONFIG))
    assert np.abs(got - want).max() > 1e-3


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_a_packed_round_with_decode_rows_then_a_block_of_two(
        model, reference, use_kernel):
    """Lane 0 holds 11 positions and rides the round with its decode row,
    lane 2 brings a chunk of 6 at position 0: ``paged_mixed_step`` on rows
    ``(1, T, n, D)``; then ``paged_decode_block`` at K = 2 from the round's
    carry.  Every pick's logits are the reference's."""
    spec, published, served = model
    hyper = reference.hyper_of(CONFIG)
    rng = np.random.default_rng(8)
    a = rng.integers(0, VOCAB, 12).tolist()
    c = rng.integers(0, VOCAB, 6).tolist()
    lanes, mp = 3, 4
    pool = PagedKVPool(n_pages=13, page_size=8, n_layers=spec.n_layers,
                       n_heads=0, head_dim=0, dtype=jnp.float32,
                       latent_width=spec.latent_width)
    tables = np.asarray([[1, 2, 3, 4], [0, 0, 0, 0], [5, 6, 7, 8]], np.int32)
    kw = dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
              compute_dtype=jnp.float32, use_kernel=use_kernel, spec=spec)
    # lane 0's first 11 positions through the padded form
    seq = np.zeros((lanes, 11), np.int32)
    seq[0] = a[:11]
    _, kv, _ = paged_ragged_forward(
        served, pool.kv, jnp.asarray(tables), jnp.asarray(seq),
        jnp.asarray([11, 0, 0], jnp.int32), jnp.asarray([11, 0, 0],
                                                        jnp.int32), **kw)
    toks, row_lane, row_off, q_lens = pack_round(lanes, {2: c}, {0: a[11]})
    zeros = np.zeros((lanes,), np.int32)
    packed = pack_words(dispatch_fields("round", lanes, mp), dict(
        tables=tables, q_lens=q_lens,
        kv_lens=np.asarray([12, 0, 6], np.int32),
        temps=np.zeros((lanes,), np.float32),
        seeds=np.zeros((lanes, 2), np.uint32),
        fresh=np.ones((lanes,), bool), rem=np.asarray([5, 0, 5], np.int32),
        stops=np.full((lanes, ROUND_STOPS), -1, np.int32),
        rows=np.stack([toks, row_lane, row_off])))
    carry = (jnp.asarray(zeros), jnp.asarray(zeros),
             jnp.zeros((lanes,), bool), jnp.asarray(zeros))
    res, last, lens, nxt, live, rem, kv = paged_mixed_step(
        served, kv, jnp.asarray(packed), carry, lanes=lanes, max_pages=mp,
        **kw)
    want_a = reference.last_logits(published, a, 1, **hyper)[0]
    want_c = reference.last_logits(published, c, 1, **hyper)[0]
    np.testing.assert_allclose(np.asarray(last)[0], want_a, rtol=5e-5,
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(last)[2], want_c, rtol=5e-5,
                               atol=5e-5)
    assert np.asarray(live).tolist() == [True, False, True]
    picks = unpack_words(result_fields(lanes, None, moe_shape(spec)),
                         np.asarray(res))["tokens"]
    assert picks[0] == want_a.argmax() and picks[2] == want_c.argmax()
    # a block of two steps from the round's carry, no lane fresh
    block = pack_words(dispatch_fields("block", lanes, mp), dict(
        tables=tables, lengths=zeros, tokens=zeros,
        active=np.zeros((lanes,), bool),
        temps=np.zeros((lanes,), np.float32),
        seeds=np.zeros((lanes, 2), np.uint32),
        fresh=np.zeros((lanes,), bool), rem=zeros,
        stops=np.full((lanes, 1), -1, np.int32)))
    out, lens, _tok, _live, _rem, kv = paged_decode_block(
        served, kv, jnp.asarray(block), (lens, nxt, live, rem), lanes=lanes,
        max_pages=mp, k=2, **kw)
    got = unpack_words(result_fields(lanes, 2, moe_shape(spec)),
                       np.asarray(out))
    assert np.asarray(lens).tolist() == [14, 0, 8]
    for lane, prompt in ((0, a), (2, c)):
        stream = [int(picks[lane])] + got["tokens"][lane].tolist()
        errs = reference.compare(published, prompt, stream,
                                 [0.0] * 3, **hyper)
        assert errs["argmax_gap"] < 1e-4
        want = reference.last_logits(published, prompt + stream[:2], 2,
                                     **hyper).astype(np.float64)
        logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
        np.testing.assert_allclose(
            got["logprobs"][lane], logp[np.arange(2), stream[1:]],
            rtol=1e-4, atol=1e-4)


# ------------------------------------------------ through the scheduler ----

def _engine(spec, params, **kw):
    kw = dict(dict(lanes=2, max_len=128, page_size=8,
                   compute_dtype=jnp.float32, prefill_chunk=8), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             **kw)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_scheduler_rounds_blocks_and_readmission_against_the_reference(
        model, reference, use_kernel):
    """Five requests on two lanes: prompts that take several mixed rounds of
    8 (decode rows riding them), decode blocks, and three re-admissions into
    lanes and pages another request left; every emitted token's
    log-probability is the reference's (logits, not tokens)."""
    spec, published, served = model
    cb = _engine(spec, served, use_kernel=use_kernel)
    try:
        assert cb.use_kernel == use_kernel
        assert cb.pool.entry_kind == "latent" and cb.pool.n_layers == 3
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n).tolist()
                   for n in (21, 37, 5, 18, 26)]
        futures = [cb.submit(p, 9, logprobs=True) for p in prompts]
        for prompt, fut in zip(prompts, futures):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(published, prompt, tokens, logprobs,
                                    **reference.hyper_of(CONFIG))
            assert got["logprob_err_max"] < 2e-4 and got["argmax_gap"] < 2e-4
        state = cb.debug_state()
        d = state["dispatch"]
        assert d["kinds"]["mixed"] >= 5 and d["kinds"]["decode"] > 0
        assert d["mixed_decode_rows"] > 0
        per_layer = np.asarray(state["moe"]["assignments"])
        assert per_layer.shape == (2, 8)
        assert (per_layer.sum(1) == 3 * (sum(map(len, prompts)) + 5 * 8)).all()
        # the streams' group: what the program holds a token, and the rows
        # that went through it, rounds and decode steps apart
        assert state["mhc"] == {
            "streams": 4, "sublayers": 6, "sinkhorn_iters": 20,
            "stream_bytes_per_row": 4 * 64 * 4,
            "rows": {"round": d["lane_work"]["round"]["rows"],
                     "decode": d["lane_work"]["decode"]["rows"]}}
        assert state["mhc"]["rows"]["round"] == sum(map(len, prompts)) + (
            d["mixed_decode_rows"])
        assert sum(state["mhc"]["rows"].values()) == sum(
            map(len, prompts)) + 5 * 8
    finally:
        cb.shutdown()


def test_no_mhc_group_without_streams():
    spec, vocab, d_ff, kw = test_engine_plan.KINDS["glm-latent-moe"]
    cb = ContinuousBatcher(init_params(spec, vocab, d_ff), spec.n_heads,
                           spec.n_layers, spec=spec,
                           compute_dtype=jnp.float32, **kw)
    try:
        assert "mhc" not in cb.debug_state()
    finally:
        cb.shutdown()


def test_debug_state_lists_the_grouped_products_the_programs_were_traced_at(
        model):
    """``debug_state()["moe"]["product"]``: both products of the expert
    layers at a decode step's ``lanes x top_k`` rows and at a round's
    ``(prompt tokens + lanes) x top_k``, written while the step programs
    were traced (here ``ragged_dot`` is kept: no TPU); a dense engine has
    no ``moe`` group."""
    spec, _, served = model
    cb = _engine(spec, served, use_kernel=False)
    try:
        cb.submit(list(range(1, 22)), 4).result(timeout=600)
        state = cb.debug_state()
    finally:
        cb.shutdown()
    product = state["moe"]["product"]
    d, f, top_k = spec.d_model, spec.moe_ff, spec.top_k
    widths = {(d, 2 * f), (f, d)}
    assert {(p["k"], p["n"]) for p in product} == widths
    assert all(p["tiles"] is None and p["vmem_bytes"] is None
               and p["dtype"] == "float32" for p in product)
    rows = {p["rows"] for p in product}
    budget = state["dispatch"]["round_budget"]
    assert {cb.lanes * top_k, (budget + cb.lanes) * top_k} <= rows
    # every row count with both of its products
    assert all({(p["k"], p["n"]) for p in product if p["rows"] == r}
               == widths for r in rows)

    from tpulab.models.transformer import init_transformer_params
    dense_spec, vocab, d_ff, kw = test_engine_plan.KINDS["dense"]
    dense = ContinuousBatcher(
        init_transformer_params(vocab=vocab, d_model=32, n_heads=2,
                                n_layers=2, d_ff=d_ff), 2, 2,
        compute_dtype=jnp.float32, **kw)
    try:
        assert dense_spec is None and "moe" not in dense.debug_state()
    finally:
        dense.shutdown()


@pytest.mark.parametrize("name, kwargs", [
    ("mesh", {"mesh": object()}),
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_dtype", {"kv_dtype": jnp.bfloat16}),
    ("kv_offload", {"kv_offload": True}),
    ("kv_publish", {"kv_publish": True}),
    ("draft_params", {"draft_params": {"layer0": {}}})])
def test_options_the_streams_and_the_latent_cache_do_not_carry_are_refused(
        model, name, kwargs):
    spec, _published, served = model
    with pytest.raises(NotImplementedError,
                       match=f"hyper-connected residual streams.*{name}"):
        _engine(spec, served, **kwargs)


# ------------------------------------- the other kinds' programs stand ----

def _lowered(spec, vocab, d_ff, kw):
    """The text (with scopes) of a decode step and a mixed round of a tiny
    engine's geometry, lowered and not run."""
    from tpulab.models.transformer import init_transformer_params
    if spec is None:
        params = init_transformer_params(vocab=vocab, d_model=32, n_heads=2,
                                         n_layers=2, d_ff=d_ff)
        heads, layers = 2, 2
    else:
        params = init_params(spec, vocab, d_ff)
        heads, layers = spec.n_heads, spec.n_layers
    cb = ContinuousBatcher(params, heads, layers, spec=spec,
                           compute_dtype=jnp.float32,
                           **dict(kw, use_kernel=False))
    try:
        lanes, fields = cb.lanes, cb.programs.fields
        size = lambda kind, tail: sum(
            int(np.prod([n for n in shape if n >= 0])) for _n, _d, shape
            in fields[kind]) + tail
        tick = jnp.zeros((size("tick", 0),), jnp.int32)
        rnd = jnp.zeros((size("round", 0) - 3 + 3 * (8 + lanes),), jnp.int32)
        carry = cb._no_carry
        texts = [
            cb.programs.tick.lower(cb.params, cb._kv_state, tick).as_text(
                debug_info=True),
            cb.programs.mixed.lower(cb.params, cb._kv_state, rnd,
                                    carry).as_text(debug_info=True)]
    finally:
        cb.shutdown()
    return "\n".join(texts)


@pytest.mark.parametrize("kind", list(test_engine_plan.KINDS) + ["longcat"])
def test_the_other_kinds_programs_hold_no_stream_scope(kind):
    """``hc_mult`` 0 adds nothing to a program: no ``mhc`` scope in the
    lowered decode step or mixed round of the seven kinds the benchmark
    already had."""
    if kind == "longcat":
        import test_longcat_flash as tl
        from tpulab.models.spec import longcat_flash_spec
        entry = (longcat_flash_spec(tl.CONFIG), tl.VOCAB, tl.D_FF,
                 dict(lanes=2, max_len=64, page_size=8))
    else:
        entry = test_engine_plan.KINDS[kind]
    text = _lowered(*entry)
    assert "mhc_" not in text and "_streams" not in text
    assert "paged_mixed_step" in text


def test_this_kinds_programs_hold_both_scopes(model):
    spec, _published, _served = model
    text = _lowered(spec, VOCAB, D_FF, dict(lanes=2, max_len=64,
                                            page_size=8))
    assert "mhc_pre" in text and "mhc_post" in text


# ------------------------------- the kernel at the published widths, Mosaic ----

@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU's compiler runs here
    without one.  Made inside a fixture, never while a module is imported:
    only the worker that is given this file loads the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("lanes, rows", [(1, 512), (32, 1)],
                         ids=["one-lane-M512", "decode"])
def test_mosaic_compiles_the_latent_kernel_at_the_cells_widths(one_chip,
                                                               lanes, rows):
    """32 heads on a latent row of 576 (stored 640 wide), the cell's 6
    layers of 20,481 pages and tables of 1,024: one chunk lane of a round
    of 512 (32 lanes x 512 rows of 32 heads are 604 MB of spread queries,
    past ``SPREAD_LIMIT_BYTES``: the lane loop) and a decode step's 32
    lanes of one row."""
    from tpulab.ops.ragged_attention import _latent_attn

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = _latent_attn.lower(
        shape(lanes, rows, 32, 576, dtype=jnp.bfloat16),
        shape(6, 20481, 1, 16, 640, dtype=jnp.bfloat16), shape(1),
        shape(lanes, 1024), shape(lanes), shape(lanes), v_width=512,
        sm_scale=192 ** -0.5, interpret=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("rows", [128, 2176], ids=["decode", "round"])
def test_mosaic_compiles_the_grouped_products_at_the_cells_widths(one_chip,
                                                                  rows):
    """Both products of an expert layer (64 experts, 3584 -> 2 x 1024 ->
    3584) at a decode step's 32 x 4 assignment rows and a round's 544 x 4,
    under the tiles the plan gives them."""
    from tpulab.ops import grouped_matmul as gm

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    for k, n in ((3584, 2048), (1024, 3584)):
        plan = gm._gmm_plan(rows, k, n, jnp.bfloat16)
        assert plan.tm == {128: 128, 2176: 544}[rows]
        compiled = gm._gmm_call.lower(
            shape(rows, k), shape(64, k, n), shape(64, dtype=jnp.int32),
            tm=plan.tm, ts=plan.ts, tn=plan.tn, interpret=False).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "grouped_matmul" in text
        assert "ragged-dot" not in text
        # no padded copy of the rows: the kernel takes them as they are
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
