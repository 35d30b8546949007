"""Gated DeltaNet layers on a matrix-valued lane state, gated attention with
partial RoPE, and a share of the routed experts with a gated shared expert.

A tiny hybrid that keeps every ratio of ``qwen3_next`` (attention on every
fourth layer, so Gated DeltaNet layers before and after one; 4 query heads on
2 KV heads of a head width that is NOT ``hidden / heads``; RoPE over a quarter
of a head; 2 key heads under 4 value heads; a 4-tap convolution; top-4 of 16
experts of which a share of 4 may be held; one shared expert behind a gate; an
untied head), held to the benchmark's plain float32 reference
(``perf/reference/qwen3_next.py``: the sequential recurrence, full attention,
a loop over the held experts, nothing imported from the program).
"""

import importlib.util
import json
import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers_engine import FirstTokenGate
from helpers_steps import mixed_step
from tpulab.engine.kv_pool import (LaneStateStore, PagedKVPool,
                                   lane_state_shapes)
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_ffn_block, _gdn_mixer, pack_round,
                                       paged_decode_step, paged_mixed_step,
                                       paged_ragged_forward)
from tpulab.models.spec import init_params, qwen3_next_spec, split_qkvz
from tpulab.ops.gated_delta_rule import (CHUNK, chunk_gated_delta_rule,
                                         gated_delta_step,
                                         one_token_gated_delta_rule)
from tpulab.ops.selective_scan import row_flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, LANES, PAGE = 97, 4, 8
CONFIG = {
    "model_type": "qwen3_next", "hidden_size": 64, "intermediate_size": 160,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "num_hidden_layers": 5,
    "full_attention_interval": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "use_sliding_window": False,
    "tie_word_embeddings": False, "vocab_size": VOCAB,
}
i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("ref_qwen3_next", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    spec = qwen3_next_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, 0, seed=3, scale=0.1)


def _kw(spec, use_kernel=False):
    return dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
                compute_dtype=jnp.float32, use_kernel=use_kernel, spec=spec)


def _fresh(spec, junk=False):
    """``(kv_pool pair, tables)``: a page store of the attention layers and
    a lane-state store, the latter filled with junk on request (what a lane
    holds after another sequence ran in it)."""
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
    """One jitted program a step function and plan (op by op, a round's
    ``lax.scan`` over its rows is compiled anew at every call)."""
    return jax.jit(partial(fn, **_kw(spec, use_kernel), **kw))


def _round(spec, params, store, tables, prefill, decode, lengths,
           use_kernel=False):
    """One ``paged_mixed_step``: ``prefill`` {lane: chunk}, ``decode`` {lane:
    token}, ``lengths`` the lanes' positions before it.  Returns ``(last
    logits (LANES, vocab), store, expert counters)``."""
    toks, row_lane, row_off, q_lens = pack_round(LANES, prefill, decode)
    kv_lens = np.asarray(lengths, np.int32) + q_lens
    kv_lens[q_lens == 0] = 0          # as the scheduler leaves idle lanes
    _nt, _lp, last, store, moe = mixed_step(
        _jitted(paged_mixed_step, spec, use_kernel, lanes=LANES, max_pages=4),
        params, store, tables, toks, row_lane, row_off, q_lens, kv_lens,
        spec=spec)
    return np.asarray(last), store, moe


def _lane_state(store, lane):
    ssm, conv = store[1]
    return np.asarray(ssm[:, lane]), np.asarray(conv[:, :, lane])


# ------------------------------------------------------------- the spec ----

def test_spec_reads_the_published_keys():
    spec = qwen3_next_spec(CONFIG)
    assert spec.mixers == ("gdn", "gdn", "gdn", "attention", "gdn")
    assert spec.state_kind == "gdn" and spec.state_layers == (0, 1, 2, 4)
    assert spec.attention_layers == (3,) and not spec.mamba_layers
    assert [spec.store_layer(i) for i in range(5)] == [0, 1, 2, 0, 3]
    assert (spec.gdn_k_heads, spec.gdn_v_heads, spec.gdn_k_dim,
            spec.gdn_v_dim, spec.d_conv) == (2, 4, 16, 16, 4)
    assert (spec.head_dim, spec.rotary_dim, spec.attn_gate, spec.qk_norm) == (
        32, 8, True, True)
    assert spec.layer_kinds == ("moe",) * 5 and spec.router == "softmax"
    assert (spec.n_experts, spec.experts_held, spec.expert_first,
            spec.n_shared, spec.shared_gate) == (16, 16, 0, 1, True)
    assert spec.cache_entry == "kv"
    hash(spec)     # it keys the jit memo
    share = qwen3_next_spec(CONFIG, first=8, held=4)
    assert (share.n_experts, share.experts_held, share.expert_first) == (
        16, 4, 8)
    with pytest.raises(ValueError, match="share"):
        qwen3_next_spec(CONFIG, first=14, held=4)


@pytest.mark.parametrize("key, value", [
    ("mlp_only_layers", [1]), ("rope_scaling", {"type": "yarn"}),
    ("use_sliding_window", True), ("decoder_sparse_step", 2)])
def test_spec_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        qwen3_next_spec(dict(CONFIG, **{key: value}))


def test_the_published_rows_layer_order_state_and_page_bytes():
    """At the benchmark's configuration: two periods, 12,877,824 B of state
    a lane, 4,096 B of pages a token, 128 of 512 experts held."""
    with open(os.path.join(ROOT, "perf", "configs",
                           "qwen3next-l8-ep4.json")) as f:
        config = json.load(f)
    share = config["share"]
    spec = qwen3_next_spec(dict(config, num_experts=share["num_experts"]),
                           first=share["first_expert"],
                           held=config["num_experts"])
    assert spec.attention_layers == (3, 7) and len(spec.state_layers) == 6
    assert (spec.n_experts, spec.experts_held, spec.top_k) == (512, 128, 10)
    assert (spec.head_dim, spec.rotary_dim) == (256, 64)
    assert sum(int(np.prod(shape)) * dtype.itemsize for shape, dtype in
               lane_state_shapes(spec, 32, jnp.bfloat16)) // 32 == 12_877_824
    assert 2 * 2 * spec.n_kv_heads * spec.head_dim * 2 == 4096


def test_gdn_leaves_follow_the_stated_initialisation(model):
    spec, params = model
    g = params["layer0"]["gdn"]
    a = np.exp(np.asarray(g["a_log"]))
    assert a.shape == (4,) and (a > 0).all() and (a <= 16).all()
    dt = np.asarray(jax.nn.softplus(g["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert np.abs(np.asarray(g["conv_w"])).max() <= 0.5
    assert g["conv_w"].shape == (4, 2 * 32 + 64)
    assert g["in_qkvz"].shape == (64, 2 * 32 + 2 * 64)
    assert "lm_head" in params and "gdn" not in params["layer3"]
    # a query head's columns are [query | gate]: q_proj twice as wide
    assert params["layer3"]["wqkv"].shape == (64, (2 * 4 + 2 * 2) * 32)
    assert params["layer0"]["shared"]["gate"].shape == (64, 1)


def test_split_qkvz_takes_the_published_interleaving_apart():
    """The published ``in_proj_qkvz`` / ``in_proj_ba`` go key head by key
    head; the served matrices hold each part's heads together.  Tagging
    every published column with (head, part, index) shows where it lands."""
    spec = qwen3_next_spec(CONFIG)            # 2 key heads, 4 value heads
    hk, rep, dk, dv = 2, 2, 16, 16
    tags = [(h, part, i) for h in range(hk)
            for part, n in (("q", dk), ("k", dk), ("v", rep * dv),
                            ("z", rep * dv)) for i in range(n)]
    published = np.arange(len(tags), dtype=np.float32)[None].repeat(3, 0)
    served = split_qkvz(published, spec)
    assert served.shape == published.shape
    want = [(h, part, i) for part, n in (("q", dk), ("k", dk),
                                         ("v", rep * dv), ("z", rep * dv))
            for h in range(hk) for i in range(n)]
    assert [tags[int(c)] for c in served[0]] == want
    # value head j = key head j // 2's v columns (j % 2) * 16 ..
    v0 = 2 * hk * dk
    assert tags[int(served[0, v0 + 3 * dv])] == (1, "v", dv)
    ba = split_qkvz(np.arange(8, dtype=np.float32)[None], spec)[0]
    # published [b0 b1 a0 a1 | b2 b3 a2 a3] -> [b0 b1 b2 b3 | a0 a1 a2 a3]
    np.testing.assert_array_equal(ba, [0, 1, 4, 5, 2, 3, 6, 7])


# --------------------------------------------------- the rule, by itself ----

def _rule_case(rng, t, segs, lanes, kh=2, vh=4, dk=16, dv=16, layers=2):
    """A packed round's rows: ``segs`` = ``(lane, rows, tokens before)``."""
    row_lane, row_off = np.full(t, -1, np.int32), np.zeros(t, np.int32)
    q_lens, kv_lens = np.zeros(lanes, np.int32), np.zeros(lanes, np.int32)
    at = 0
    for lane, n, before in segs:
        row_lane[at:at + n], row_off[at:at + n] = lane, np.arange(n)
        q_lens[lane], kv_lens[lane] = n, n + before
        at += n
    unit = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.standard_normal((t, kh, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((t, kh, dk)))
    v = rng.standard_normal((t, vh, dv))
    g = -np.exp(rng.uniform(-4, 1, (t, vh)))
    beta = 1 / (1 + np.exp(-rng.standard_normal((t, vh))))
    states = rng.standard_normal((layers, lanes, vh, dk, dv))
    return (q, k, v, g, beta, states, row_lane, row_off, q_lens, kv_lens)


def _by_hand(q, k, v, g, beta, states, row_lane, row_off, q_lens, kv_lens,
             layer):
    """The recurrence in float64 numpy, row by row."""
    t, vh = g.shape
    rep = vh // q.shape[1]
    states = states.copy()
    out = np.zeros(v.shape)
    for r in range(t):
        lane = row_lane[r]
        if lane < 0:
            continue
        if row_off[r] == 0:
            s = (np.zeros_like(states[layer, lane])
                 if kv_lens[lane] == q_lens[lane]
                 else states[layer, lane].copy())
        for j in range(vh):
            kk, qq = k[r, j // rep], q[r, j // rep]
            s[j] = np.exp(g[r, j]) * s[j]
            d = beta[r, j] * (v[r, j] - s[j].T @ kk)
            s[j] = s[j] + np.outer(kk, d)
            out[r, j] = s[j].T @ qq
        if row_off[r] == q_lens[lane] - 1:
            states[layer, lane] = s
    return out, states


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel-interpret"])
@pytest.mark.parametrize("t, segs", [
    (3 * CHUNK, [(2, 100, 0), (0, 70, 40), (3, 1, 9), (1, 17, 0)]),
    (2 * CHUNK, [(1, 2 * CHUNK, 512)]),
    (21, [(3, 5, 0), (1, 1, 7), (0, 9, 2)])],
    ids=["several-lanes-boundaries-mid-segment", "one-lane-whole-chunks",
         "no-whole-chunk"])
def test_both_forms_of_the_rule_against_the_recurrence_by_hand(use_kernel, t,
                                                               segs):
    """Segments of several lanes in one packed round (first chunks from
    zeros over junk, later chunks from the slot, a single row), chunk
    boundaries in the middle of a segment and segment boundaries in the
    middle of a chunk: the chunk kernel (interpreter) and the ``lax.scan``
    form give the outputs and the final states of the recurrence."""
    case = _rule_case(np.random.default_rng(len(segs)), t, segs, lanes=5)
    q, k, v, g, beta, states, row_lane, row_off, q_lens, kv_lens = case
    want_o, want_s = _by_hand(*case, layer=1)
    f32 = lambda x: jnp.asarray(x.reshape(t, -1), jnp.float32)   # noqa: E731
    flags = row_flags(i32(row_lane), i32(row_off), i32(q_lens), i32(kv_lens))
    o, s = chunk_gated_delta_rule(
        f32(q), f32(k), f32(v), f32(g), f32(beta),
        jnp.asarray(states, jnp.float32), 1, i32(row_lane), flags,
        use_kernel=use_kernel)
    live = row_lane >= 0
    np.testing.assert_allclose(np.asarray(o)[live],
                               want_o.reshape(t, -1)[live], rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=5e-6)
    # layer 0 never moved, nor the lane without a segment
    np.testing.assert_array_equal(np.asarray(s)[0], states[0].astype(
        np.float32))
    np.testing.assert_array_equal(np.asarray(s)[1, 4], states[1, 4].astype(
        np.float32))


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel-interpret"])
def test_a_round_moves_the_slots_of_its_segments_and_no_other(use_kernel):
    """A store that holds a distinct value a slot (layer, lane, head) and a
    round whose segments touch two lanes of eight, one from its slot and
    one from zeros: the six other slots of the layer and every other layer
    are what they were, bit for bit, and the two are written from their
    segments' last rows (the kernel copies no block of the store: it loads
    and stores slots)."""
    lanes, layers, t = 8, 3, CHUNK + 9
    case = _rule_case(np.random.default_rng(5), t, [(6, CHUNK - 3, 11),
                                                    (2, 12, 0)], lanes,
                      layers=layers)
    q, k, v, g, beta, states, row_lane, row_off, q_lens, kv_lens = case
    slot = np.arange(layers * lanes * 4, dtype=np.float64).reshape(
        layers, lanes, 4, 1, 1)
    states = np.broadcast_to(1 + slot / 8, states.shape).copy()
    _want_o, want_s = _by_hand(q, k, v, g, beta, states, *case[6:], layer=1)
    f32 = lambda x: jnp.asarray(x.reshape(t, -1), jnp.float32)   # noqa: E731
    flags = row_flags(i32(row_lane), i32(row_off), i32(q_lens), i32(kv_lens))
    before = states.astype(np.float32)
    _o, s = chunk_gated_delta_rule(
        f32(q), f32(k), f32(v), f32(g), f32(beta), jnp.asarray(before), 1,
        i32(row_lane), flags, use_kernel=use_kernel)
    s = np.asarray(s)
    others = [lane for lane in range(lanes) if lane not in (2, 6)]
    np.testing.assert_array_equal(s[1][others], before[1][others])
    np.testing.assert_array_equal(s[[0, 2]], before[[0, 2]])
    np.testing.assert_allclose(s[1][[2, 6]], want_s[1][[2, 6]], rtol=2e-5,
                               atol=5e-6)
    assert (s[1][[2, 6]] != before[1][[2, 6]]).any(axis=(1, 2, 3)).all()


#: the lanes of a one-token step: ``(lane, tokens before it)`` of those that
#: hold a row, among ``lanes``, and the lanes whose slots hold junk that is
#: not a number
ONE_TOKEN = {
    "all-live": (3, [(0, 5), (1, 2), (2, 9)], []),
    "some-dead": (5, [(1, 5), (3, 1)], []),
    "a-fresh-lane-over-garbage": (4, [(0, 3), (2, 0), (3, 7)], [2]),
    "one-lane": (1, [(0, 4)], []),
}


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel-interpret"])
@pytest.mark.parametrize("case", list(ONE_TOKEN))
def test_one_token_rule_is_the_recurrence(model, use_kernel, case):
    """One row a lane on the state store, in XLA and as the kernel
    ``gated_delta_step`` (interpreter): a live lane's output and new slot
    are the recurrence's by hand (a lane at position 0 from zeros, over a
    slot of NaNs); the slots of the lanes without a row and the store's
    other layers are what they were, bit for bit; and through the mixer
    of a whole layer so is the convolution's tail."""
    lanes, rows, garbage = ONE_TOKEN[case]
    rng = np.random.default_rng(7)
    live = np.zeros(lanes, bool)
    live[[lane for lane, _ in rows]] = True
    fresh = np.zeros(lanes, bool)
    fresh[[lane for lane, before in rows if before == 0]] = True
    # row b is lane b's: a dead lane's row is there and is nobody's
    q, k, v, g, beta, states, *_ = _rule_case(
        rng, lanes, [(lane, 1, 1) for lane in range(lanes)], lanes, layers=3)
    states[:, garbage] = np.nan
    row_lane = np.where(live, np.arange(lanes), -1).astype(np.int32)
    q_lens, kv_lens = live.astype(np.int32), np.zeros(lanes, np.int32)
    for lane, before in rows:
        kv_lens[lane] = before + 1
    want_o, want_s = _by_hand(q, k, v, g, beta, states, row_lane,
                              np.zeros(lanes, np.int32), q_lens, kv_lens,
                              layer=1)
    def f32(x):
        return jnp.asarray(x, jnp.float32).reshape(lanes, -1)
    before = np.asarray(states, np.float32)
    o, s = one_token_gated_delta_rule(
        f32(q), f32(k), f32(v), f32(g), f32(beta), jnp.asarray(before), 1,
        jnp.asarray(live), jnp.asarray(fresh), use_kernel=use_kernel)
    o, s = np.asarray(o), np.asarray(s)
    np.testing.assert_allclose(o[live], want_o.reshape(lanes, -1)[live],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(s[1][live], want_s[1][live], rtol=2e-5,
                               atol=5e-6)
    assert np.isfinite(s[1][live]).all()
    same = lambda a, b: a.tobytes() == b.tobytes()          # noqa: E731
    assert same(s[1][~live], before[1][~live])
    assert same(s[0], before[0]) and same(s[2], before[2])
    # gated_delta_step, the definition, on the slots themselves
    s0 = jnp.where(jnp.asarray(fresh)[:, None, None, None], 0.0, before[1])
    o_def, s_def = gated_delta_step(
        jnp.asarray(np.repeat(q, 2, 1), jnp.float32),
        jnp.asarray(np.repeat(k, 2, 1), jnp.float32),
        jnp.asarray(v, jnp.float32), jnp.asarray(g, jnp.float32),
        jnp.asarray(beta, jnp.float32), s0)
    np.testing.assert_allclose(o[live], np.asarray(o_def).reshape(
        lanes, -1)[live], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(s[1][live], np.asarray(s_def)[live],
                               rtol=1e-6, atol=1e-7)

    # a whole layer's mixer at the same lanes: the tail of the convolution
    spec, params = model
    ssm, conv = (jnp.asarray(rng.standard_normal(a.shape), jnp.float32)
                 for a in LaneStateStore(spec, lanes, jnp.float32).arrays)
    h = jnp.asarray(rng.standard_normal((lanes, 1, spec.d_model)),
                    jnp.float32)
    _out, (ssm1, conv1) = _gdn_mixer(
        spec, params["layer1"]["gdn"], 1, h, jnp.asarray(kv_lens - 1)[:, None],
        jnp.asarray(live)[:, None], (ssm, conv), dict(use_kernel=use_kernel),
        jnp.float32)
    for was, now in ((np.asarray(ssm), np.asarray(ssm1)),
                     (np.asarray(conv).swapaxes(1, 2),
                      np.asarray(conv1).swapaxes(1, 2))):
        assert same(now[1][~live], was[1][~live])
        assert same(np.delete(now, 1, 0), np.delete(was, 1, 0))
        assert not (now[1][live] == was[1][live]).all()


# ----------------------------------------------- the steps, the reference ----

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_one_chunk_uneven_chunks_and_token_by_token_agree(model, reference,
                                                          use_kernel):
    """A 21-token prompt through mixed rounds in one chunk, in chunks of 8,
    3, 1 and 9, and token by token through decode steps, all over a store
    full of junk: the same logits at the last position, the same lane
    state, and the reference's logits."""
    spec, params = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, 21)
    want = reference.last_logits(params, tokens.tolist(), 1,
                                 **reference.hyper_of(CONFIG))[0]
    outs = []
    for sizes in ([21], [8, 3, 1, 9]):
        store, tables = _fresh(spec, junk=True)
        at = 0
        for n in sizes:
            last, store, _ = _round(spec, params, store, tables,
                                    {1: tokens[at:at + n]}, {},
                                    [0, at, 0, 0], use_kernel)
            at += n
        outs.append((last[1], _lane_state(store, 1)))
    store, tables = _fresh(spec, junk=True)
    step = _jitted(paged_decode_step, spec, use_kernel)
    for pos, tok in enumerate(tokens):
        logits, store, _ = step(
            params, store, tables, i32([0, pos, 0, 0]), i32([0, tok, 0, 0]),
            jnp.asarray([False, True, False, False]))
    outs.append((np.asarray(logits)[1], _lane_state(store, 1)))
    for logits, (ssm, conv) in outs:
        np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(ssm, outs[0][1][0], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(conv, outs[0][1][1], rtol=1e-4, atol=1e-5)
    # the lanes that ran nothing still hold what they held
    assert (_lane_state(store, 0)[0] == 3).all()
    assert (_lane_state(store, 3)[1] == 3).all()


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
@pytest.mark.parametrize("first_chunk", [True, False],
                         ids=["two-chunks", "a-chunk-and-an-idle-lane"])
def test_a_round_of_several_lanes_is_each_lane_alone(model, use_kernel,
                                                     first_chunk):
    """Two lanes' chunks (one a first chunk, one a later chunk) and two
    other lanes' decode rows in ONE packed round give, lane for lane, the
    logits and the state of four rounds that carry one lane each: the
    chunk rows through the chunk kernel, the decode rows beside them
    through the one-token kernel, on one store.  Without the first chunk
    its lane is idle over junk, which the round leaves as it was, bit for
    bit, in every layer."""
    spec, params = model
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, VOCAB, n) for n in (14, 9, 12, 7)]

    def warm(store, tables):
        """Lanes 0, 1 and 3 have a past: 9, 8 and 6 tokens."""
        return _round(spec, params, store, tables,
                      {0: seqs[0][:9], 1: seqs[1][:8], 3: seqs[3][:6]}, {},
                      [0, 0, 0, 0], use_kernel)[1]

    lengths = [9, 8, 0, 6]
    prefill = {0: seqs[0][9:14]}                     # a later chunk
    if first_chunk:
        prefill[2] = seqs[2]                         # and a first chunk
    decode = {1: int(seqs[1][8]), 3: int(seqs[3][6])}
    store, tables = _fresh(spec, junk=True)
    together, store, _ = _round(spec, params, warm(store, tables), tables,
                                prefill, decode, lengths, use_kernel)
    for lane in range(LANES):
        if lane == 2 and not first_chunk:
            ssm, conv = _lane_state(store, lane)
            assert (ssm == 3).all() and (conv == 3).all()
            continue
        alone, tables = _fresh(spec, junk=True)
        last, alone, _ = _round(
            spec, params, warm(alone, tables), tables,
            {k: v for k, v in prefill.items() if k == lane},
            {k: v for k, v in decode.items() if k == lane}, lengths,
            use_kernel)
        np.testing.assert_allclose(together[lane], last[lane], rtol=1e-4,
                                   atol=1e-4)
        for a, b in zip(_lane_state(store, lane), _lane_state(alone, lane)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_padded_form_refuses_gdn_layers(model):
    spec, params = model
    store, tables = _fresh(spec)
    with pytest.raises(NotImplementedError, match="padded"):
        paged_ragged_forward(params, store, tables,
                             jnp.zeros((LANES, 4), jnp.int32),
                             i32([4, 0, 0, 0]), i32([4, 0, 0, 0]),
                             **_kw(spec))


# ------------------------------------------------- the share of the experts ----

def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(
        model, reference):
    """The served expert block at four shares of four experts each: their
    routed parts, with the gated shared expert counted once, add up to what
    the uncut reference gives for the whole layer; each share's counters
    cover the router's 16 columns, and its ``experts hit`` its own four."""
    spec, params = model
    p = params["layer1"]
    x = jnp.asarray(np.random.default_rng(11).standard_normal((1, 24, 64)),
                    jnp.float32)
    valid = jnp.ones((1, 24), bool)
    want = reference.moe(x[0], p, eps=spec.rms_eps, top_k=spec.top_k, first=0)
    whole, stats = _ffn_block(spec, p, 1, x, valid, jnp.float32)
    np.testing.assert_allclose(np.asarray(whole - x)[0], want, rtol=2e-5,
                               atol=2e-6)
    shared = np.asarray(reference.moe(x[0], dict(p, moe=dict(
        p["moe"], w13=p["moe"]["w13"][:0], w2=p["moe"]["w2"][:0])),
        eps=spec.rms_eps, top_k=spec.top_k, first=0))
    parts, hits = [], 0
    for first in (0, 4, 8, 12):
        share = qwen3_next_spec(CONFIG, first=first, held=4)
        held = dict(p, moe=dict(p["moe"],
                                w13=p["moe"]["w13"][first:first + 4],
                                w2=p["moe"]["w2"][first:first + 4]))
        y, st = _ffn_block(share, held, 1, x, valid, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(y - x)[0],
            reference.moe(x[0], held, eps=spec.rms_eps, top_k=spec.top_k,
                          first=first), rtol=2e-5, atol=2e-6)
        parts.append(np.asarray(y - x)[0] - shared)
        st = np.asarray(st)
        np.testing.assert_array_equal(st[:16], np.asarray(stats)[:16])
        assert st[16] == (st[first:first + 4] > 0).sum() and st[17] == 1
        hits += st[16]
    assert hits == np.asarray(stats)[16]
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=5e-6)
    assert np.abs(shared).max() > 1e-3      # the gate did not close it


# ------------------------------------------------ through the scheduler ----

def _engine(spec, params, **kw):
    kw = dict(dict(lanes=3, max_len=64, page_size=PAGE,
                   compute_dtype=jnp.float32, prefill_chunk=8), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             **kw)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_tiny_hybrid_end_to_end_against_the_reference(model, reference,
                                                      use_kernel):
    """Three chunks of 8 through mixed rounds, then decode blocks through
    the state: every emitted token's log-probability is the reference's."""
    spec, params = model
    cb = _engine(spec, params, use_kernel=use_kernel)
    try:
        assert cb.use_kernel == use_kernel
        assert cb.pool.n_layers == 1
        assert cb.pool.bytes_per_token == 2 * 2 * 32 * 4
        prompt = np.random.default_rng(1).integers(0, VOCAB, 21).tolist()
        toks, lps = cb.submit(prompt, steps=10, logprobs=True).result(
            timeout=300)
        got = reference.compare(params, prompt, toks, lps,
                                **reference.hyper_of(CONFIG))
        assert got["logprob_err_max"] < 2e-4 and got["argmax_gap"] == 0
        state = cb.debug_state()["state"]
        assert state["kind"] == "gdn" and state["lanes"] == 3
        assert state["zero_starts"] == 1
        assert state["bytes_per_lane"] == 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
        assert state["hbm_bytes"] == 3 * state["bytes_per_lane"]
        moe = cb.debug_state()["moe"]
        assert (moe["first"], moe["held"]) == (0, 16)
        assert moe["assignments_here"] == [sum(a) for a in moe["assignments"]]
        assert sum(moe["assignments_here"]) == 5 * 4 * (21 + 9)
    finally:
        cb.shutdown()


def test_what_a_finished_stream_leaves_in_the_stores_is_the_references(
        model, reference):
    """``debug_state()["last_release"]`` names the lane and the pages of the
    request that ended last; its slot and its pages hold, until another
    request takes them, the state and the K/V rows after every token but
    the last one emitted: the reference's own, which is what the
    benchmark's ``correct`` holds a served state and K/V store to."""
    spec, params = model
    cb = _engine(spec, params)
    try:
        assert cb.debug_state()["last_release"] is None
        rng = np.random.default_rng(4)
        for n, steps in ((21, 10), (5, 4)):     # the second reuses lane 0
            prompt = rng.integers(0, VOCAB, n).tolist()
            toks, lps = cb.submit(prompt, steps=steps, logprobs=True).result(
                timeout=300)
            held = cb.debug_state()["last_release"]
            assert held["length"] == n + steps - 1
            assert len(held["pages"]) == -(-held["length"] // PAGE)
            state = np.asarray(cb.state.arrays[0][0, held["lane"]])
            kv = np.asarray(cb.pool.kv[0, np.asarray(held["pages"])])
            kv = np.moveaxis(kv, 1, 0).reshape(2, -1, kv.shape[-1])
            got = reference.token_errors(
                params, prompt, toks, lps,
                stores=(state, kv[:, :held["length"]]),
                **reference.hyper_of(CONFIG))
            assert got["state_err"] < 1e-5 and got["kv_err"] < 1e-5
            assert got["logprob_err"].max() < 2e-4
            # one token fewer or one more is another state
            _logits, want = reference.last_logits(
                params, prompt + toks[:-2], 1, stores=True,
                **reference.hyper_of(CONFIG))
            off = reference.store_errors(state, kv[:, :held["length"] - 1],
                                         want)
            assert off["state_err"] > 1e-2
    finally:
        cb.shutdown()


def test_lane_work_counts_the_lanes_and_keys_each_program_ran(model):
    """A prompt of 21 in chunks of 8 is three rounds of one lane whose
    segments end at 8, 16 and 21 keys; its 9 decode steps read 22 .. 30
    keys.  A second stream beside it: a round's decoding lane is a pass of
    one row under "round", a block's lanes a pass a step each."""
    spec, params = model
    cb = _engine(spec, params)
    try:
        prompt = np.random.default_rng(2).integers(0, VOCAB, 21).tolist()
        cb.submit(prompt, steps=10).result(timeout=300)
        d = cb.debug_state()["dispatch"]
        assert d["lane_work"]["round"] == {"passes": 3, "rows": 21,
                                           "keys": 8 + 16 + 21}
        assert d["lane_work"]["decode"] == {"passes": 9, "rows": 9,
                                            "keys": sum(range(22, 31))}
        assert d["kinds"]["mixed"] == 3 and d["decode_block_steps"] >= 9
        # the slots (a lane's state in one of the four Gated DeltaNet
        # layers) the dispatches held, and those a lane with a row touched
        state = cb.debug_state()["state"]
        assert state["rule"] == {"decode": "xla", "round": "xla"}
        assert state["slots"] == {
            "round": {"held": 3 * state["lanes"] * 4, "touched": 3 * 4},
            "decode": {"held": d["decode_block_steps"] * state["lanes"] * 4,
                       "touched": 9 * 4}}
        assert state["lanes"] == 3
        futures = [cb.submit(prompt[:n], steps=6) for n in (13, 7)]
        for f in futures:
            f.result(timeout=300)
        w = cb.debug_state()["dispatch"]["lane_work"]
        rows = w["round"]["rows"] + w["decode"]["rows"]
        assert rows == 21 + 9 + (13 + 5) + (7 + 5)
        assert w["decode"]["passes"] == w["decode"]["rows"]
        assert w["round"]["passes"] <= w["round"]["rows"]
        assert w["round"]["keys"] + w["decode"]["keys"] > 45 + 234
    finally:
        cb.shutdown()


def test_a_share_of_the_experts_through_the_scheduler(model, reference):
    """The engine told it holds experts 4 .. 8 of 16: the reference with the
    same share agrees on every token, the counters say which assignments
    were made here, and ``experts_hit`` counts held experts alone."""
    spec, params = model
    share = qwen3_next_spec(CONFIG, first=4, held=4)
    held = dict(params)
    for i in range(spec.n_layers):
        m = params[f"layer{i}"]["moe"]
        held[f"layer{i}"] = dict(params[f"layer{i}"], moe=dict(
            m, w13=m["w13"][4:8], w2=m["w2"][4:8]))
    cb = _engine(share, held)
    try:
        prompt = np.random.default_rng(12).integers(0, VOCAB, 13).tolist()
        toks, lps = cb.submit(prompt, steps=8, logprobs=True).result(
            timeout=300)
        got = reference.compare(
            held, prompt, toks, lps,
            **reference.hyper_of(dict(CONFIG, share={"first_expert": 4})))
        assert got["logprob_err_max"] < 2e-4 and got["argmax_gap"] == 0
        moe = cb.debug_state()["moe"]
        assert (moe["first"], moe["held"]) == (4, 4)
        assert moe["assignments_here"] == [sum(a[4:8])
                                           for a in moe["assignments"]]
        total = sum(map(sum, moe["assignments"]))
        assert total == 5 * 4 * (13 + 7)
        assert 0 < sum(moe["assignments_here"]) < total
        assert moe["decode_steps"] == 7
        assert moe["experts_hit"] <= 5 * 4 * moe["decode_steps"]
    finally:
        cb.shutdown()


def _fresh_tokens(spec, params, prompt, steps):
    cb = _engine(spec, params, lanes=1)
    try:
        return cb.submit(prompt, steps).result(timeout=300)
    finally:
        cb.shutdown()


def test_a_reused_lane_gives_the_tokens_of_a_fresh_engine(model):
    """Four requests through ONE lane, one after the other: each starts
    from zeros on the device whatever its predecessor left in the slot."""
    spec, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (11, 5, 17, 8)]
    cb = _engine(spec, params, lanes=1)
    try:
        got = [cb.submit(p, 9).result(timeout=300) for p in prompts]
        assert cb.debug_state()["state"]["zero_starts"] == 4
    finally:
        cb.shutdown()
    assert got == [_fresh_tokens(spec, params, p, 9) for p in prompts]


def test_a_preempted_request_resumes_with_a_fresh_engines_tokens(model):
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
    dict(prefix_cache=True), dict(kv_offload=True), dict(mesh=object())],
    ids=["prefix_cache", "kv_offload", "mesh"])
def test_options_the_lane_state_does_not_carry_are_refused_by_name(
        model, option, request):
    """And the message names the spec's own kinds, not a hand-written
    list."""
    spec, params = model
    name = request.node.callspec.id
    with pytest.raises(NotImplementedError, match=name) as err:
        _engine(spec, params, **option)
    assert "gdn layers" in str(err.value) and "moe FFNs" in str(err.value)
    assert "Mamba" not in str(err.value)


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


@pytest.mark.parametrize("rows", [512, 256, 1, -32, -1],
                         ids=["M512", "M256", "M1", "one-token-32-lanes",
                              "one-token-1-lane"])
def test_mosaic_compiles_the_chunk_kernel_at_the_published_widths(one_chip,
                                                                  rows):
    """Qwen3-Next's widths (16 key heads under 32 value heads of 128), the
    cell's 32 lanes and 6 layers of state, a full round's chunk rows (512;
    256 before PR 42) and the smallest (one row, no whole chunk); and the
    one-token kernel at the cell's 32 lanes and at one: what the interpreter
    cannot refuse, Mosaic can (tiling, VMEM, a transposed product, a
    transpose of the key and query heads)."""
    from tpulab.ops.gated_delta_rule import _rule_call, _step_call

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    i32s = lambda *dims: shape(*dims, dtype=jnp.int32)      # noqa: E731
    if rows < 0:
        lanes = -rows
        compiled = _step_call.lower(
            shape(lanes, 2048), shape(lanes, 2048), shape(lanes, 4096),
            shape(lanes, 32), shape(lanes, 32),
            shape(6, lanes, 32, 128, 128), i32s(1),
            shape(lanes, dtype=jnp.bool_), shape(lanes, dtype=jnp.bool_),
            interpret=False).compile()
    else:
        compiled = _rule_call.lower(
            shape(rows, 2048), shape(rows, 2048), shape(rows, 4096),
            shape(rows, 32), shape(rows, 32), shape(6, 32, 32, 128, 128),
            i32s(1), i32s(rows), i32s(rows), interpret=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
