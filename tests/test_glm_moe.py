"""A latent (MLA) cache entry and a routed expert FFN through the paged engine.

Tiny widths that keep every ratio of ``glm4_moe_lite`` (a low-rank query,
a latent narrower than the heads it expands to, nope != v != rope, 8
experts top-2 plus a shared one, one dense layer then two expert layers, a
non-zero selection bias), held to the benchmark's plain float32 reference
(``perf/reference/glm4_moe_lite.py``: expanded attention, a loop over
experts, nothing imported from the program).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import paged_decode_step, paged_ragged_forward
from tpulab.models.spec import glm4_moe_lite_spec, init_params, split_kv_b
from tpulab.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, D_FF = 97, 96
CONFIG = {
    "model_type": "glm4_moe_lite", "hidden_size": 64, "intermediate_size": D_FF,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "moe_intermediate_size": 48, "routed_scaling_factor": 1.8,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "rope_scaling": None,
    "partial_rotary_factor": 1, "attention_bias": False, "vocab_size": VOCAB,
}


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "glm4_moe_lite.py")
    spec = importlib.util.spec_from_file_location("ref_glm4_moe_lite", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    spec = glm4_moe_lite_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, D_FF, seed=3, scale=0.1)


def _hyper(reference, n_layers=3):
    return reference.hyper_of(dict(CONFIG, num_hidden_layers=n_layers))


def _forward(spec, params, tokens, use_kernel, chunk=None, n_layers=None):
    """``tokens`` through ``paged_ragged_forward`` in chunks against a
    fresh latent pool: logits at every position, one lane of two used."""
    n_layers = n_layers or spec.n_layers
    pool = PagedKVPool(n_pages=9, page_size=8, n_layers=n_layers, n_heads=0,
                       head_dim=0, dtype=jnp.float32,
                       latent_width=spec.latent_width)
    kv, tables = pool.kv, jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    chunk = chunk or len(tokens)
    rows = []
    for s in range(0, len(tokens), chunk):
        part = list(tokens[s:s + chunk])
        seq = np.zeros((2, chunk), np.int32)
        seq[0, :len(part)] = part
        logits, kv, *_stats = paged_ragged_forward(
            params, kv, tables, jnp.asarray(seq),
            jnp.asarray([len(part), 0], jnp.int32),
            jnp.asarray([s + len(part), 0], jnp.int32), n_heads=spec.n_heads,
            n_layers=n_layers, compute_dtype=jnp.float32,
            use_kernel=use_kernel, spec=spec)
        rows.append(np.asarray(logits)[0, :len(part)])
    return np.concatenate(rows), kv


def test_spec_reads_the_published_keys():
    spec = glm4_moe_lite_spec(CONFIG)
    assert spec.cache_entry == "latent" and spec.latent_width == 40
    assert spec.layer_kinds == ("dense", "moe", "moe")
    assert spec.moe_layers == (1, 2) and spec.qk_head_dim == 20
    assert (spec.rms_eps, spec.routed_scale, spec.top_k) == (1e-5, 1.8, 2)
    hash(spec)     # it keys the jit memo
    for key, value in (("n_group", 2), ("rope_scaling", {"factor": 2}),
                       ("partial_rotary_factor", 0.5),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            glm4_moe_lite_spec(dict(CONFIG, **{key: value}))


@pytest.mark.parametrize("n_layers", [1, 2, 3],
                         ids=["dense-layer", "expert-layer", "whole-model"])
def test_layer_block_and_model_match_the_plain_reference(model, reference,
                                                         n_layers):
    """The absorbed form from a latent page against the expanded form with
    per-head keys and values; the grouped experts against the loop."""
    spec, params = model
    spec = glm4_moe_lite_spec(dict(CONFIG, num_hidden_layers=n_layers))
    tokens = np.random.default_rng(1).integers(0, VOCAB, 21).tolist()
    got, _ = _forward(spec, params, tokens, use_kernel=False)
    want = reference.last_logits(params, tokens, len(tokens),
                                 **_hyper(reference, n_layers))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_chunked_prefill_then_decode_steps_match_the_full_forward(
        model, reference, use_kernel):
    """Three chunks through the ragged forward, then single-token decode
    steps through the latent cache, against ONE full forward."""
    spec, params = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, 26).tolist()
    want = reference.last_logits(params, tokens, len(tokens),
                                 **_hyper(reference))
    got, kv = _forward(spec, params, tokens[:21], use_kernel, chunk=8)
    np.testing.assert_allclose(got, want[:21], rtol=3e-5, atol=3e-5)
    tables = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    for pos in range(21, 26):
        logits, kv, stats = paged_decode_step(
            params, kv, tables, jnp.asarray([pos, 0], jnp.int32),
            jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([True, False]), n_heads=spec.n_heads,
            n_layers=spec.n_layers, compute_dtype=jnp.float32,
            use_kernel=use_kernel, spec=spec)
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos],
                                   rtol=3e-5, atol=3e-5)
        stats = np.asarray(stats)
        # one live row: top-2 of 8 in each expert layer, one live step
        assert stats.shape == (2, 8 + 2)
        assert (stats[:, :8].sum(1) == 2).all()
        assert (stats[:, 8] == 2).all() and (stats[:, 9] == 1).all()


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_continuous_batcher_serves_it_on_the_ragged_plan(model, reference,
                                                         use_kernel):
    """Prefill in chunks, then decode blocks, through the scheduler: the
    emitted tokens' log-probabilities against the reference's full
    forward; the pool holds one latent row a token a layer."""
    spec, params = model
    cb = ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                           lanes=3, max_len=128, page_size=8,
                           compute_dtype=jnp.float32, use_kernel=use_kernel,
                           prefill_chunk=16)
    try:
        assert cb.pool.entry_kind == "latent"
        assert cb.pool.kv.shape == (3, cb.pool.n_pages, 1, 8, 128)
        assert cb.pool.bytes_per_token == 3 * 128 * 4
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n).tolist() for n in (5, 37, 18)]
        futures = [cb.submit(p, 7, logprobs=True) for p in prompts]
        for prompt, fut in zip(prompts, futures):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(params, prompt, tokens, logprobs,
                                    **_hyper(reference))
            assert got["logprob_err_max"] < 5e-5 and got["argmax_gap"] < 5e-5
            assert got["flipped_share"] == 0
        state = cb.debug_state()
        assert state["pool"]["entry_kind"] == "latent"
        assert state["pool"]["bytes_per_token"] == 3 * 128 * 4
        moe_state = state["moe"]
        assert moe_state["expert_layers"] == [1, 2]
        per_layer = np.asarray(moe_state["assignments"]).sum(axis=1)
        # every prompt position and every decode step of every stream, top-2
        # (a stream's last token is emitted, never fed back)
        assert (per_layer == 2 * (sum(map(len, prompts)) + 3 * 6)).all()
        assert moe_state["decode_steps"] > 0
        assert 2 * moe_state["decode_steps"] * 2 <= moe_state["experts_hit"] \
            <= 2 * moe_state["decode_steps"] * 6
        assert state["dispatch"]["kinds"]["mixed"] > 0
    finally:
        cb.shutdown()


# ------------------------------------------------------------- the router ---

def test_router_chooses_with_the_bias_and_weights_without_it():
    """Hand-made scores: an identity router, so ``s = sigmoid(x)``."""
    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                     [0.5, 0.4, 0.3, 0.2]])
    bias = jnp.asarray([-1.0, 0.0, 0.0, 0.5])
    idx, w = moe.route(jnp.eye(4), x, 2, "sigmoid_bias", bias, scale=1.8)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x)))
    # row 0: s + b = [-.119, .731, .5, .769] -> experts 3, 1; the bias
    # moves the choice (without it: 0, 1) and is not in the weights
    assert np.asarray(idx).tolist() == [[3, 1], [3, 1]]
    for r in range(2):
        chosen = s[r, [3, 1]]
        np.testing.assert_allclose(np.asarray(w)[r],
                                   1.8 * chosen / chosen.sum(), rtol=1e-6)
    _, w_raw = moe.route(jnp.eye(4), x, 2, "sigmoid_bias", bias, norm=False)
    np.testing.assert_allclose(np.asarray(w_raw), s[:, [3, 1]], rtol=1e-6)
    idx_sm, w_sm = moe.route(jnp.eye(4), x, 2)          # the softmax router
    assert np.asarray(idx_sm).tolist() == [[0, 1], [0, 1]]
    np.testing.assert_allclose(np.asarray(w_sm).sum(-1), 1.0, rtol=1e-6)


def _loop_over_experts(x, idx, w, w_in, w_out, act):
    out = np.zeros((x.shape[0], w_out.shape[-1]), np.float64)
    f = w_out.shape[1]
    for n in range(x.shape[0]):
        for e, wt in zip(np.asarray(idx)[n], np.asarray(w)[n]):
            h = np.asarray(x, np.float64)[n] @ np.asarray(w_in, np.float64)[e]
            if act == "swiglu":
                h = h[:f] / (1.0 + np.exp(-h[:f])) * h[f:]
            else:
                h = np.asarray(jax.nn.gelu(jnp.asarray(h, jnp.float32)),
                               np.float64)
            out[n] += wt * (h @ np.asarray(w_out, np.float64)[e])
    return out


@pytest.mark.parametrize("act", ["swiglu", "gelu"])
def test_grouped_experts_equal_the_loop_over_experts(act):
    rng = np.random.default_rng(5)
    n, d, f, e, k = 19, 16, 12, 8, 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(e, d, 2 * f if act == "swiglu" else f))
                       * 0.3, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * 0.3, jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(e)[:k] for _ in range(n)]),
                      jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    got = moe.expert_ffn(x, idx, w, w_in, w_out, act)
    np.testing.assert_allclose(np.asarray(got),
                               _loop_over_experts(x, idx, w, w_in, w_out, act),
                               rtol=2e-5, atol=2e-5)


def test_no_row_is_dropped_when_every_row_chooses_the_same_expert():
    """No capacity: 40 rows, all on experts 5 and 2."""
    rng = np.random.default_rng(6)
    n, d, f, e = 40, 16, 12, 8
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.3, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * 0.3, jnp.float32)
    idx = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (n, 1))
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, 2)), jnp.float32)
    got = np.asarray(moe.expert_ffn(x, idx, w, w_in, w_out))
    want = _loop_over_experts(x, idx, w, w_in, w_out, "swiglu")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert (np.abs(got).sum(axis=1) > 0).all()
    stats = np.asarray(moe.routing_stats(idx, e, valid=jnp.arange(n) < 30))
    assert stats.tolist() == [0, 0, 30, 0, 0, 30, 0, 0, 2, 1]


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """An expert layer told which experts it holds computes their part:
    the parts of two halves add up to the layer."""
    rng = np.random.default_rng(8)
    n, d, f, e = 11, 16, 12, 8
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.3, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(e, f, d)) * 0.3, jnp.float32)
    idx, w = moe.route(jnp.asarray(rng.normal(size=(d, e)), jnp.float32), x,
                       3, "sigmoid_bias", jnp.zeros((e,)))
    whole = moe.expert_ffn(x, idx, w, w_in, w_out)
    parts = [moe.expert_ffn(x, idx, w, w_in[s:s + 4], w_out[s:s + 4],
                            first=s) for s in (0, 4)]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), rtol=2e-5, atol=2e-5)


def test_split_kv_b_gives_the_absorbed_halves():
    spec = glm4_moe_lite_spec(CONFIG)
    kv_b = np.random.default_rng(4).normal(
        size=(32, 4 * (12 + 16))).astype(np.float32)
    w_uk, w_uv = split_kv_b(jnp.asarray(kv_b), spec)
    assert w_uk.shape == (4, 12, 32) and w_uv.shape == (4, 32, 16)
    c = np.random.default_rng(5).normal(size=(32,)).astype(np.float32)
    expanded = (c @ kv_b).reshape(4, 28)        # per head [k_nope | v]
    np.testing.assert_allclose(np.einsum("hnc,c->hn", w_uk, c),
                               expanded[:, :12], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.einsum("hcv,c->hv", w_uv, c),
                               expanded[:, 12:], rtol=1e-5, atol=1e-6)


# ------------------------------------------------- what is not carried yet ---

@pytest.mark.parametrize("name,kwargs", [
    ("draft_params", dict(draft_params={"layer0": {}})),
    ("mesh", dict(mesh="a mesh")),
    ("kv_offload", dict(kv_offload=True)),
    ("kv_publish", dict(kv_publish=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_dtype", dict(kv_dtype=jnp.float8_e4m3fn)),
    ("hbm", dict(hbm="an arbiter")),
])
def test_each_option_the_latent_cache_does_not_carry_is_refused_by_name(
        model, name, kwargs):
    spec, params = model
    with pytest.raises(NotImplementedError, match=name.split("=")[0]):
        ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                          lanes=2, max_len=64, page_size=8,
                          compute_dtype=jnp.float32, **kwargs)


def test_latent_pool_has_no_host_side_format():
    pool = PagedKVPool(n_pages=4, page_size=8, n_layers=2, n_heads=0,
                       head_dim=0, dtype=jnp.float32, latent_width=40)
    with pytest.raises(NotImplementedError, match="latent"):
        pool.host_shape(2)
    with pytest.raises(ValueError, match="kind"):
        spec = glm4_moe_lite_spec(CONFIG)
        ContinuousBatcher(init_params(spec, VOCAB, D_FF), spec.n_heads,
                          spec.n_layers, spec=spec, lanes=2, max_len=64,
                          page_size=8, pool=PagedKVPool(
                              n_pages=4, page_size=8, n_layers=3, n_heads=4,
                              head_dim=16, dtype=jnp.float32))
