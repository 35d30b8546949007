"""LongCat-Flash through the paged engine: a published layer of two latent
attentions, two dense FFNs and one expert block on a shortcut, served as two
engine layers; a router whose last columns are identity experts.

Tiny widths that keep every ratio of ``longcat_flash`` (a low-rank query
whose factor is 2, a latent narrower than the heads it expands to, nope != v
!= rope, 8 FFN experts and 4 identity columns at top-3, a non-zero selection
bias at the scale of the scores), held to the benchmark's plain float32
reference (``perf/reference/longcat_flash.py``: expanded attention with the
two factors applied and interleaved RoPE, a loop over experts, nothing
imported from the program).  The reference reads the PUBLISHED attention
matrices, the engine what :func:`longcat_flash_layout` makes of them.
"""

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine import paged_steps
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_ffn_block, _layer_block, moe_shape,
                                       paged_decode_step,
                                       paged_ragged_forward)
from tpulab.models.spec import (ModelSpec, init_params, longcat_flash_layout,
                                longcat_flash_spec, mla_scales)
from tpulab.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, D_FF = 97, 96
CONFIG = {
    "model_type": "longcat_flash", "hidden_size": 64, "ffn_hidden_size": D_FF,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 12, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 8, "zero_expert_num": 4, "moe_topk": 3,
    "zero_expert_type": "identity", "attention_method": "MLA",
    "attention_bias": False, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
    "vocab_size": VOCAB,
}
#: the published configuration as the benchmark cuts it (ISSUE 46)
PUBLISHED = dict(
    CONFIG, hidden_size=6144, ffn_hidden_size=12288,
    expert_ffn_hidden_size=2048, num_layers=4, num_attention_heads=64,
    kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, n_routed_experts=512, zero_expert_num=256,
    moe_topk=12, vocab_size=16384)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("ref_longcat_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _lay_out(spec, params, seed, q_scale, kv_scale):
    """``(published, served)`` trees of ``params`` (``init_params``): its
    ``wq_b`` / ``wkv_a`` taken as the published matrices, a ``kv_b_proj``
    drawn beside them; ``served`` is what the program's layout makes of
    them."""
    rng = np.random.default_rng(seed)
    published, served = dict(params), dict(params)
    width = spec.n_heads * (spec.qk_nope_head_dim + spec.v_head_dim)
    for i in range(spec.n_layers):
        p = params[f"layer{i}"]
        kv_b = jnp.asarray(0.1 * rng.standard_normal(
            (spec.kv_lora_rank, width)), jnp.float32)
        wq_b, wkv_a, w_uk, w_uv = longcat_flash_layout(
            p["wq_b"], p["wkv_a"], kv_b, spec, q_scale, kv_scale)
        published[f"layer{i}"] = {k: v for k, v in dict(p, kv_b=kv_b).items()
                                  if k not in ("w_uk", "w_uv")}
        served[f"layer{i}"] = dict(p, wq_b=wq_b, wkv_a=wkv_a, w_uk=w_uk,
                                   w_uv=w_uv)
    return published, served


@pytest.fixture(scope="module")
def model():
    spec = longcat_flash_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    params = init_params(spec, VOCAB, D_FF, seed=3, scale=0.1)
    return (spec,) + _lay_out(spec, params, 5, *mla_scales(CONFIG))


def _share(tree, spec, first, held):
    """``tree`` holding experts ``first .. first + held`` alone."""
    out = dict(tree)
    for i in spec.moe_layers:
        m = tree[f"layer{i}"]["moe"]
        out[f"layer{i}"] = dict(tree[f"layer{i}"], moe=dict(
            m, w13=m["w13"][first:first + held],
            w2=m["w2"][first:first + held]))
    return out


def _forward(spec, params, tokens, use_kernel, chunk=None):
    """``tokens`` through ``paged_ragged_forward`` in chunks against a fresh
    latent pool: logits at every position, one lane of two used."""
    pool = PagedKVPool(n_pages=9, page_size=8, n_layers=spec.n_layers,
                       n_heads=0, head_dim=0, dtype=jnp.float32,
                       latent_width=spec.latent_width)
    kv, tables = pool.kv, jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    chunk = chunk or len(tokens)
    rows, stats = [], []
    for s in range(0, len(tokens), chunk):
        part = list(tokens[s:s + chunk])
        seq = np.zeros((2, chunk), np.int32)
        seq[0, :len(part)] = part
        logits, kv, st = paged_ragged_forward(
            params, kv, tables, jnp.asarray(seq),
            jnp.asarray([len(part), 0], jnp.int32),
            jnp.asarray([s + len(part), 0], jnp.int32), n_heads=spec.n_heads,
            n_layers=spec.n_layers, compute_dtype=jnp.float32,
            use_kernel=use_kernel, spec=spec)
        rows.append(np.asarray(logits)[0, :len(part)])
        stats.append(np.asarray(st))
    return np.concatenate(rows), kv, sum(stats)


# ------------------------------------------------------------- the spec ----

def test_spec_reads_the_published_keys():
    spec = longcat_flash_spec(CONFIG)
    assert spec.n_layers == 4 and spec.cache_entry == "latent"
    assert spec.layer_kinds == ("shortcut", "dense") * 2
    assert spec.moe_layers == (0, 2) and spec.attention_layers == (0, 1, 2, 3)
    assert [spec.store_layer(i) for i in range(4)] == [0, 1, 2, 3]
    assert (spec.n_experts, spec.zero_experts, spec.ffn_experts) == (12, 4, 8)
    assert (spec.top_k, spec.routed_scale, spec.norm_topk) == (3, 6.0, False)
    assert spec.router == "softmax_bias" and spec.n_shared == 0
    assert (spec.expert_first, spec.experts_held) == (0, 8)
    assert (spec.latent_width, spec.qk_head_dim, spec.rms_eps) == (40, 20,
                                                                   1e-5)
    assert moe_shape(spec) == (2, 12 + 2)
    share = longcat_flash_spec(CONFIG, first=4, held=2)
    assert (share.expert_first, share.experts_held, share.n_experts) == (
        4, 2, 12)
    hash(spec)     # it keys the jit memo
    assert mla_scales(CONFIG) == (2.0, 2.0 ** 0.5)
    assert mla_scales(dict(CONFIG, mla_scale_q_lora=False,
                           mla_scale_kv_lora=False)) == (1.0, 1.0)


@pytest.mark.parametrize("key, value", [
    ("zero_expert_type", "copy"), ("rope_scaling", {"factor": 4}),
    ("attention_bias", True), ("router_bias", True),
    ("norm_topk_prob", True), ("attention_method", "MHA")])
def test_spec_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        longcat_flash_spec(dict(CONFIG, **{key: value}))


@pytest.mark.parametrize("first, held", [(6, 4), (-1, 2), (0, 9), (8, 1)])
def test_spec_refuses_a_share_that_is_no_range_of_the_ffn_experts(first,
                                                                  held):
    """The identity columns are nobody's share: a range ends at column 8."""
    with pytest.raises(ValueError, match="not a share of the router's 8"):
        longcat_flash_spec(CONFIG, first=first, held=held)


@pytest.mark.parametrize("kw, match", [
    (dict(layer_kinds=("dense", "shortcut")), "followed by a dense layer"),
    (dict(layer_kinds=("shortcut", "moe")), "followed by a dense layer"),
    (dict(layer_kinds=("shortcut", "shortcut")), "followed by a dense"),
    (dict(layer_kinds=("shortcut", "dense"), mixers=("mamba", "attention"),
          d_inner=8, d_state=4, d_conv=4, dt_rank=2), "every mixer is"),
    (dict(zero_experts=8), "zero_experts 8"),
    (dict(router="softmax_sigmoid"), "unknown router kind")])
def test_model_spec_refuses_a_shortcut_with_nowhere_to_land(kw, match):
    base = dict(n_layers=2, d_model=16, n_heads=2, n_kv_heads=2, head_dim=8,
                n_experts=8, top_k=2, moe_ff=8)
    with pytest.raises(ValueError, match=match):
        ModelSpec(**dict(base, **kw))


def test_parameter_and_cache_byte_counts_are_the_issues():
    """At the published widths as the cell cuts them: 5,172.6 M parameters
    in the matrices (16 of 512 experts, 4 of 28 layers, an eighth of the
    vocabulary) and 8 latent rows of 576 values a token, 9,216 B in bf16.
    The page store pads a row to whole 128-lane tiles (640), so the POOL
    holds 10,240 B a token: ``latent_page_shape``'s rule, as kind
    ``glm4_moe_lite``'s 576 -> 640."""
    spec = longcat_flash_spec(PUBLISHED, first=0, held=16)
    tree = jax.eval_shape(partial(init_params, spec, 16384, 12288))
    leaves = jax.tree_util.tree_leaves(tree)
    matrices = sum(int(np.prod(x.shape)) for x in leaves if len(x.shape) > 1)
    assert round(matrices / 1e6, 1) == 5172.6
    assert spec.n_layers == 8 and spec.latent_width == 576
    assert spec.n_layers * spec.latent_width * 2 == 9216
    pool = PagedKVPool(n_pages=3, page_size=16, n_layers=spec.n_layers,
                       n_heads=0, head_dim=0, dtype=jnp.bfloat16,
                       latent_width=spec.latent_width)
    assert pool.kv.shape == (8, 3, 1, 16, 640)
    assert pool.bytes_per_token == 8 * 640 * 2
    layer = tree["layer0"]
    assert layer["moe"]["router"].shape == (6144, 768)
    assert layer["moe"]["w13"].shape == (16, 6144, 4096)
    assert "moe" not in tree["layer1"] and "w1" in layer and "w1" in tree[
        "layer1"]


# --------------------------------------------------- the layout at load ----

def test_layout_folds_the_factors_and_turns_the_rope_columns(model,
                                                             reference):
    """The served matrices against the published ones: ``wq_b`` doubled,
    a head's rope columns ``[even | odd]``; ``wkv_a``'s likewise; the two
    halves of ``kv_b_proj`` times the latent factor."""
    spec, published, served = model
    pub, got = published["layer1"], served["layer1"]
    nope, rope = spec.qk_nope_head_dim, spec.qk_rope_head_dim
    q_pub = np.asarray(pub["wq_b"]).reshape(-1, spec.n_heads, nope + rope)
    q_got = np.asarray(got["wq_b"]).reshape(-1, spec.n_heads, nope + rope)
    np.testing.assert_array_equal(q_got[..., :nope], 2 * q_pub[..., :nope])
    np.testing.assert_array_equal(q_got[..., nope:nope + rope // 2],
                                  2 * q_pub[..., nope::2])
    np.testing.assert_array_equal(q_got[..., nope + rope // 2:],
                                  2 * q_pub[..., nope + 1::2])
    c = spec.kv_lora_rank
    np.testing.assert_array_equal(np.asarray(got["wkv_a"])[:, :c],
                                  np.asarray(pub["wkv_a"])[:, :c])
    np.testing.assert_array_equal(np.asarray(got["wkv_a"])[:, c + rope // 2:],
                                  np.asarray(pub["wkv_a"])[:, c + 1::2])
    kv_b = np.asarray(pub["kv_b"]).reshape(c, spec.n_heads, -1)
    np.testing.assert_allclose(np.asarray(got["w_uk"]), 2 ** 0.5 * kv_b[
        :, :, :nope].transpose(1, 2, 0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["w_uv"]), 2 ** 0.5 * kv_b[
        :, :, nope:].transpose(1, 0, 2), rtol=1e-6)


@pytest.mark.parametrize("q_scale, kv_scale", [(1.0, 2 ** 0.5), (2.0, 1.0)],
                         ids=["no-query-factor", "no-latent-factor"])
def test_a_factor_left_out_at_load_shows_in_the_logits(model, reference,
                                                       q_scale, kv_scale):
    """The unfolded reference against a layout that drops one factor: the
    logits move by far more than the agreement of the true layout."""
    spec, published, _served = model
    params = init_params(spec, VOCAB, D_FF, seed=3, scale=0.1)
    _pub, wrong = _lay_out(spec, params, 5, q_scale, kv_scale)
    tokens = np.random.default_rng(1).integers(0, VOCAB, 13).tolist()
    got, _, _ = _forward(spec, wrong, tokens, use_kernel=False)
    want = reference.last_logits(published, tokens, len(tokens),
                                 **reference.hyper_of(CONFIG))
    assert np.abs(got - want).max() > 1e-3


# ------------------------------------------------------ the router, by hand ----

def test_router_chooses_by_score_plus_bias_and_weighs_six_times_the_score():
    """Softmax over ALL columns; the choice by ``s + b``; the weight ``6 s``
    of the chosen, not renormalised."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((9, 16)).astype(np.float32)
    w = rng.standard_normal((16, 12)).astype(np.float32)
    bias = (rng.standard_normal(12) / 12).astype(np.float32)
    idx, weights = moe.route(jnp.asarray(w), jnp.asarray(x), 3,
                             "softmax_bias", jnp.asarray(bias), scale=6.0,
                             norm=False)
    logits = x.astype(np.float64) @ w.astype(np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    want = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want, -1))
    np.testing.assert_allclose(
        np.sort(np.asarray(weights), -1),
        np.sort(6 * np.take_along_axis(s, want, -1), -1), rtol=1e-5)
    # the bias moved a choice, and the weights do not sum to the scale
    assert (np.sort(want, -1) != np.sort(np.argsort(-s, -1)[:, :3], -1)).any()
    assert np.abs(np.asarray(weights).sum(-1) - 6).min() > 0.1


def test_a_row_that_chose_only_identity_columns_returns_weight_times_h(model):
    """With the identity columns' bias out of reach every row chooses three
    of them: ``MoE(h) = 6 (s_a + s_b + s_c) h``, no assignment is to an FFN
    expert and no expert is hit."""
    spec, _published, served = model
    p = served["layer0"]
    bias = jnp.where(jnp.arange(12) >= 8, 10.0, p["moe"]["bias"])
    p = dict(p, moe=dict(p["moe"], bias=bias))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 5, 64)),
                    jnp.float32)
    (dense, m), stats = _ffn_block(spec, p, 0, x, jnp.ones((1, 5), bool),
                                   jnp.float32)
    from tpulab.models.transformer import _rmsnorm
    h = np.asarray(_rmsnorm(x, p["ln2"]["scale"], spec.rms_eps))[0]
    logits = h @ np.asarray(p["moe"]["router"])
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    top3 = np.sort(s[:, 8:], -1)[:, -3:].sum(-1)
    np.testing.assert_allclose(np.asarray(m)[0], 6 * top3[:, None] * h,
                               rtol=1e-5, atol=1e-6)
    stats = np.asarray(stats)
    assert stats[:8].sum() == 0 and stats[8:12].sum() == 3 * 5
    assert stats[12] == 0 and stats[13] == 1      # no expert hit; had work


# ------------------------------------------------------- the layer, by hand ----

def test_the_shortcut_lands_after_the_second_ffn_not_before_the_second_mla(
        model, reference):
    """Two tokens through the two engine layers of one published layer
    against the published equations written out; the same equations with
    ``m`` added a sublayer early (at ``b1``, before ``MLA_1``) are not what
    the engine computes."""
    spec, published, served = model
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)
    hyper = reference.hyper_of(CONFIG)
    attn = dict(theta=hyper["rope_theta"], n_heads=hyper["n_heads"],
                nope=hyper["nope"], v_dim=hyper["v_dim"],
                q_scale=hyper["q_scale"], kv_scale=hyper["kv_scale"])
    p0, p1 = published["layer0"], published["layer1"]
    eps = spec.rms_eps
    leaves = reference.ATTENTION_LEAVES

    def by_hand(early: bool):
        a1 = reference.mla(x, {k: p0[k] for k in leaves}, eps=eps, **attn)
        h1 = reference._norm(a1, p0["ln2"]["scale"], eps=eps)
        m = reference.moe(h1, p0["moe"], top_k=3, scale=6.0, n_zero=4)
        b1 = a1 + reference.swiglu(h1, p0["w1"], p0["w3"], p0["w2"])
        if early:
            b1 = b1 + m
        a2 = reference.mla(b1, {k: p1[k] for k in leaves}, eps=eps, **attn)
        h2 = reference._norm(a2, p1["ln2"]["scale"], eps=eps)
        out = a2 + reference.swiglu(h2, p1["w1"], p1["w3"], p1["w2"])
        return np.asarray(out if early else out + m), np.asarray(m)

    want, m_want = by_hand(False)
    np.testing.assert_allclose(
        want, np.asarray(reference.layer(
            x, p0, p1, eps=eps, attn=attn, top_k=3, scale=6.0, n_zero=4,
            first=0)), rtol=1e-6, atol=1e-6)
    pool = PagedKVPool(n_pages=3, page_size=8, n_layers=spec.n_layers,
                       n_heads=0, head_dim=0, dtype=jnp.float32,
                       latent_width=spec.latent_width)
    pos = jnp.arange(2)[None]
    valid = jnp.ones((1, 2), bool)
    seg = dict(tables=jnp.asarray([[1]], jnp.int32),
               q_lens=jnp.asarray([2], jnp.int32),
               kv_lens=jnp.asarray([2], jnp.int32), use_kernel=False,
               kernel_geometry=None, mesh=None)
    page, slot = jnp.ones((1, 2), jnp.int32), pos
    carried, kv, stats = _layer_block(spec, served["layer0"], 0, x[None], pos,
                                      valid, pool.kv, page, slot, seg,
                                      jnp.float32)
    assert isinstance(carried, tuple) and stats is not None
    np.testing.assert_allclose(np.asarray(carried[1])[0], m_want, rtol=2e-5,
                               atol=2e-6)
    got, kv, stats = _layer_block(spec, served["layer1"], 1, carried, pos,
                                  valid, kv, page, slot, seg, jnp.float32)
    assert stats is None
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-5, atol=2e-5)
    early, _ = by_hand(True)
    assert np.abs(early - want).max() > 1e-2
    assert np.abs(m_want).max() > 1e-2       # the block adds something


# ------------------------------------------------- the share of the experts ----

def test_the_routed_parts_of_all_shares_and_the_identity_part_once_add_up(
        model, reference):
    """The served expert block at four shares of two experts each: their
    routed parts, with the identity part counted ONCE, add up to what the
    uncut reference gives for the whole block; each share's counters cover
    the router's 12 columns, its ``experts hit`` its own two."""
    spec, published, served = model
    p = served["layer2"]
    x = jnp.asarray(np.random.default_rng(11).standard_normal((1, 24, 64)),
                    jnp.float32)
    valid = jnp.ones((1, 24), bool)
    from tpulab.models.transformer import _rmsnorm
    h = _rmsnorm(x, p["ln2"]["scale"], spec.rms_eps)[0]
    kw = dict(top_k=3, scale=6.0, n_zero=4)
    want = np.asarray(reference.moe(h, p["moe"], **kw))
    (_, whole), stats = _ffn_block(spec, p, 2, x, valid, jnp.float32)
    np.testing.assert_allclose(np.asarray(whole)[0], want, rtol=2e-5,
                               atol=2e-6)
    identity = np.asarray(reference.moe(h, p["moe"], routed=False, **kw))
    assert np.abs(identity).max() > 1e-3     # some row chose such a column
    parts, hits = [], 0
    for first in (0, 2, 4, 6):
        share = longcat_flash_spec(CONFIG, first=first, held=2)
        held = _share(served, spec, first, 2)["layer2"]
        (_, m), st = _ffn_block(share, held, 2, x, valid, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(m)[0], reference.moe(h, held["moe"], first=first,
                                            **kw), rtol=2e-5, atol=2e-6)
        parts.append(np.asarray(m)[0] - identity)
        st = np.asarray(st)
        np.testing.assert_array_equal(st[:12], np.asarray(stats)[:12])
        assert st[12] == (st[first:first + 2] > 0).sum() and st[13] == 1
        hits += st[12]
    assert hits == np.asarray(stats)[12]
    np.testing.assert_allclose(sum(parts) + identity, want, rtol=2e-5,
                               atol=5e-6)


# ------------------------------------------------- the step programs ----

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_chunked_prefill_then_decode_steps_match_the_full_forward(
        model, reference, use_kernel):
    """Three chunks through the ragged forward, then single-token decode
    steps through the latent cache, against ONE full forward of the
    unfolded reference."""
    spec, published, served = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, 26).tolist()
    want = reference.last_logits(published, tokens, len(tokens),
                                 **reference.hyper_of(CONFIG))
    got, kv, stats = _forward(spec, served, tokens[:21], use_kernel, chunk=8)
    np.testing.assert_allclose(got, want[:21], rtol=3e-5, atol=3e-5)
    # every row chose three of the router's twelve columns in both blocks
    assert stats.shape == (2, 12 + 2)
    assert (stats[:, :12].sum(1) == 3 * 21).all()
    tables = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    for pos in range(21, 26):
        logits, kv, st = paged_decode_step(
            served, kv, tables, jnp.asarray([pos, 0], jnp.int32),
            jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([True, False]), n_heads=spec.n_heads,
            n_layers=spec.n_layers, compute_dtype=jnp.float32,
            use_kernel=use_kernel, spec=spec)
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos],
                                   rtol=3e-5, atol=3e-5)
        st = np.asarray(st)
        assert (st[:, :12].sum(1) == 3).all() and (st[:, 13] == 1).all()
        # what the step reads of the routed weights: the FFN columns chosen
        np.testing.assert_array_equal(st[:, 12], (st[:, :8] > 0).sum(1))


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
    8, decode blocks, and three re-admissions into lanes and pages another
    request left; every emitted token's log-probability is the unfolded
    reference's (logits, not tokens)."""
    spec, published, served = model
    cb = _engine(spec, served, use_kernel=use_kernel)
    try:
        assert cb.use_kernel == use_kernel
        assert cb.pool.entry_kind == "latent" and cb.pool.n_layers == 4
        assert cb.pool.bytes_per_token == 4 * 128 * 4
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n).tolist()
                   for n in (21, 37, 5, 18, 26)]
        futures = [cb.submit(p, 9, logprobs=True) for p in prompts]
        for prompt, fut in zip(prompts, futures):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(published, prompt, tokens, logprobs,
                                    **reference.hyper_of(CONFIG))
            assert got["logprob_err_max"] < 1e-4 and got["argmax_gap"] < 1e-4
        state = cb.debug_state()
        assert state["dispatch"]["kinds"]["mixed"] >= 5
        assert state["dispatch"]["kinds"]["decode"] > 0
        got = state["moe"]
        assert got["expert_layers"] == [0, 2]
        assert (got["zero_first"], got["zero_columns"]) == (8, 4)
        assert (got["first"], got["held"]) == (0, 8)
        per_layer = np.asarray(got["assignments"])
        assert per_layer.shape == (2, 12)
        # every prompt position and every decode step of every stream, top-3
        # (a stream's last token is emitted, never fed back)
        assert (per_layer.sum(1) == 3 * (sum(map(len, prompts)) + 5 * 8)).all()
        assert got["assignments_here"] == per_layer[:, :8].sum(1).tolist()
        assert 0 < per_layer[:, 8:].sum() < per_layer.sum()
        assert got["decode_steps"] > 0
    finally:
        cb.shutdown()


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_a_wide_rounds_chunk_rows_go_through_attention_a_lane_at_a_time(
        model, reference, use_kernel, monkeypatch):
    """With the limit on the spread's bytes at 0 every round takes the
    lane loop (``_chunk_lanes``): rounds that carry the chunks of two and
    three lanes at once (a budget of 64 over prompts of 5-37), in the order
    their rows are packed, give the unfolded reference's logits, as the
    padded form does."""
    spec, published, served = model
    monkeypatch.setattr(paged_steps, "SPREAD_LIMIT_BYTES", 0)
    paged_steps._JIT_MEMO.clear()
    cb = _engine(spec, served, use_kernel=use_kernel, lanes=3,
                 prefill_chunk=None)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, VOCAB, n).tolist()
                   for n in (21, 37, 5, 18, 26, 33)]
        futures = [cb.submit(p, 6, logprobs=True) for p in prompts]
        for prompt, fut in zip(prompts, futures):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(published, prompt, tokens, logprobs,
                                    **reference.hyper_of(CONFIG))
            assert got["logprob_err_max"] < 1e-4 and got["argmax_gap"] < 1e-4
        d = cb.debug_state()["dispatch"]
        # some round carried more than one lane's chunk
        assert d["lane_work"]["round"]["passes"] > d["kinds"]["mixed"]
        assert d["round_budget"] == 64
    finally:
        cb.shutdown()
        paged_steps._JIT_MEMO.clear()


def test_a_share_of_the_experts_through_the_scheduler(model, reference):
    """The engine told it holds FFN experts 4 .. 6 of 8: the reference with
    the same share agrees on every token, and the counters say which
    assignments were made here."""
    spec, published, served = model
    share = longcat_flash_spec(CONFIG, first=4, held=2)
    cb = _engine(share, _share(served, spec, 4, 2))
    try:
        prompt = np.random.default_rng(12).integers(0, VOCAB, 13).tolist()
        toks, lps = cb.submit(prompt, steps=8, logprobs=True).result(
            timeout=300)
        got = reference.compare(
            _share(published, spec, 4, 2), prompt, toks, lps,
            **reference.hyper_of(dict(CONFIG, share={"first_expert": 4})))
        assert got["logprob_err_max"] < 1e-4 and got["argmax_gap"] < 1e-4
        got = cb.debug_state()["moe"]
        assert (got["first"], got["held"], got["zero_first"]) == (4, 2, 8)
        assert got["assignments_here"] == [sum(a[4:6])
                                           for a in got["assignments"]]
        total = sum(map(sum, got["assignments"]))
        assert total == 2 * 3 * (13 + 7)
        assert 0 < sum(got["assignments_here"]) < total
        assert got["experts_hit"] <= 2 * 2 * got["decode_steps"]
    finally:
        cb.shutdown()


@pytest.mark.parametrize("name, kwargs", [
    ("mesh", {"mesh": object()}),
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_dtype", {"kv_dtype": jnp.bfloat16})])
def test_options_the_latent_cache_does_not_carry_are_refused_by_name(
        model, name, kwargs):
    spec, _published, served = model
    with pytest.raises(NotImplementedError, match=name):
        _engine(spec, served, **kwargs)


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


@pytest.mark.parametrize("lanes, rows", [(1, 512), (1, 128), (32, 1)],
                         ids=["one-lane-M512", "one-lane-M128", "decode"])
def test_mosaic_compiles_the_latent_kernel_at_the_cells_widths(one_chip,
                                                               lanes, rows):
    """64 heads on a latent row of 576 (stored 640 wide), the cell's 8
    layers of 16,385 pages and tables of 1,024: one chunk lane of a wide
    round (``_chunk_lanes``: 512 rows, and 128, the narrowest round that
    takes the loop) and a decode step's 32 lanes of one row: what the
    interpreter cannot refuse, Mosaic can (tiling, VMEM)."""
    from tpulab.ops.ragged_attention import _latent_attn

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = _latent_attn.lower(
        shape(lanes, rows, 64, 576, dtype=jnp.bfloat16),
        shape(8, 16385, 1, 16, 640, dtype=jnp.bfloat16), shape(1),
        shape(lanes, 1024), shape(lanes), shape(lanes), v_width=512,
        sm_scale=192 ** -0.5, interpret=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
