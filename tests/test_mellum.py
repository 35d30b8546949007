"""Mellum2 through the paged engine: window layers beside full ones on a page
table a layer KIND (the page store in two layer groups, the window group's
blocks going back to it while the request lives), YaRN on the full layers and
plain RoPE on the window layers, behind top-2 of 8 softmax-routed experts.

Tiny widths that keep every ratio of ``mellum`` (4 query heads on 2 KV heads,
three window layers to one full layer, a window of 24 keys, 8 experts), held
to the benchmark's plain float32 reference (``perf/reference/mellum.py``: one
causal forward over the whole sequence, the window a mask over the full score
matrix, a loop over experts, nothing imported from the program) and to
computations written out by hand.  The key block of the kernels' walk is cut
to 32 rows here (``_TARGET_BLOCK_ROWS``; 256 on the chip) so that a tiny
sequence crosses several: the unit the window group is taken and returned in.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_engine_plan
from helpers_engine import FirstTokenGate
from helpers_steps import decode_block, mixed_step
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_gather_attend, dispatch_fields,
                                       pack_round, paged_decode_block,
                                       paged_mixed_step)
from tpulab.engine.plan import plan_engine
from tpulab.models.spec import ModelSpec, init_params, mellum_spec
from tpulab.ops import ragged_attention as ra

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, PAGE, WINDOW, BLOCK_ROWS = 97, 8, 24, 32
ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                       "original_max_position_embeddings": 32,
                       "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.1386294361119891},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
CONFIG = {
    "model_type": "mellum", "attention_bias": False, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "max_position_embeddings": 512,
    "max_window_layers": 0, "moe_intermediate_size": 16,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_parameters": ROPE,
    "sliding_window": WINDOW, "tie_word_embeddings": False,
    "vocab_size": VOCAB, "use_sliding_window": True}
#: the published configuration (the catalog's ``config``) at the depth the
#: benchmark cuts it to (ISSUE 56)
PUBLISHED = dict(
    CONFIG, head_dim=128, hidden_size=2304, intermediate_size=7168,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    mlp_layer_types=["sparse"] * 8, max_position_embeddings=131072,
    moe_intermediate_size=896, num_attention_heads=32, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=8, num_key_value_heads=4,
    sliding_window=1024, vocab_size=98304, rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}})
i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The walk's key block at 32 rows (4 pages of 8) in every test here."""
    monkeypatch.setattr(ra, "_TARGET_BLOCK_ROWS", BLOCK_ROWS)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "mellum.py")
    spec = importlib.util.spec_from_file_location("ref_mellum", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    spec = mellum_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, 0, seed=5, scale=0.1)


def _engine(spec, params, use_kernel=False, lanes=2, **kw):
    kw = dict(dict(max_len=256, page_size=PAGE, n_pages=1 + 32 * lanes,
                   prefill_chunk=64), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             lanes=lanes, compute_dtype=jnp.float32,
                             use_kernel=use_kernel, **kw)


# ------------------------------------------------------------------ the spec --

def test_spec_reads_the_published_keys():
    sp = mellum_spec(PUBLISHED)
    assert (sp.n_layers, sp.d_model, sp.n_heads, sp.n_kv_heads, sp.head_dim
            ) == (8, 2304, 32, 4, 128)
    assert (sp.n_experts, sp.top_k, sp.moe_ff, sp.n_shared, sp.router,
            sp.norm_topk, sp.qk_norm) == (64, 8, 896, 0, "softmax", True,
                                          True)
    assert sp.window == 1024 and sp.attn_kinds == (
        "window", "window", "window", "full") * 2
    assert sp.page_groups == (("full", 2), ("window", 6))
    assert [sp.store_layer(i) for i in range(8)] == [0, 1, 2, 0, 3, 4, 5, 1]
    assert [sp.layer_window(i) for i in range(8)] == [
        1024, 1024, 1024, 0] * 2
    assert sp.rope_theta == 500000.0 and sp.rope_scaling == (
        16.0, 8192.0, 32.0, 1.0)
    assert sp.rope_factor == 1.2772588722239782
    assert sp.cache_entry == "kv" and sp.state_kind is None
    assert sp.layer_kinds == ("moe",) * 8 and sp.moe_layers == tuple(range(8))
    # the 28 published layers: seven periods
    whole = mellum_spec(dict(PUBLISHED, num_hidden_layers=28,
                             layer_types=PUBLISHED["layer_types"][:4] * 7,
                             mlp_layer_types=["sparse"] * 28))
    assert whole.page_groups == (("full", 7), ("window", 21))


def test_parameter_and_cache_byte_counts_are_the_issues(monkeypatch):
    """ISSUE 56's arithmetic, from the program's own tree and stores."""
    monkeypatch.setattr(ra, "_TARGET_BLOCK_ROWS", 256)     # the chip's
    sp = mellum_spec(PUBLISHED)
    tree = jax.eval_shape(lambda: init_params(sp, 98304, 0))
    count = lambda t: sum(int(np.prod(x.shape))        # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    layer = tree["layer0"]
    assert count(layer["wqkv"]) + count(layer["wo"]) == 21_233_664
    assert count(layer["moe"]["router"]) == 147_456
    assert count(layer["moe"]["w13"]) + count(layer["moe"]["w2"]) == (
        396_361_728)
    assert count(layer) == 417_747_712            # 417.75 M a layer
    assert count(tree["embed"]) + count(tree["lm_head"]) == 452_984_832
    assert count(tree) == 3_794_968_832           # 7.59 GB in bf16
    # K/V: 2,048 B a token a layer; 4,096 B a token in the full group,
    # 12,288 B a ROW of a lane's window blocks
    from tpulab.engine.kv_pool import kv_page_shape
    page = int(np.prod(kv_page_shape(16, 4, 128))) * 2
    assert page // 16 == 2048
    assert dict(sp.page_groups)["full"] * page // 16 == 4096
    assert dict(sp.page_groups)["window"] * page // 16 == 12288
    plan = plan_engine(
        spec=sp, n_heads=32, n_layers=8, n_kv_heads=4, rope_theta=None,
        d_model=2304, vocab=98304, lanes=32, max_len=32768, page_size=16,
        prefill_chunk=None, use_kernel=True, compute_dtype=jnp.bfloat16,
        kv_dtype=None, round_ceiling=512, kernel_auto_min_ctx=2048)
    assert (plan.pool_layers, plan.window_layers, plan.window) == (2, 6, 1024)
    assert plan.max_pages == 2048 and plan.round_cap == 512
    # the window group a lane: the 256-row blocks that overlap 1,024 keys
    # and a round's 512 rows at their widest
    assert plan.walk_block_pages == 16
    assert plan.window_lane_pages(512) == 112       # 1,024 + 512 + 256 rows
    # 32 lanes of it: 0.70 GB beside the full group's 2.15 GB
    assert 32 * 112 * 16 * 12288 == 704_643_072


@pytest.mark.parametrize("key, value, match", [
    ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse"],
     "mlp_layer_types"),
    ("layer_types", ["sliding_attention"] * 3 + ["chunked_attention"],
     "layer_types"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("attention_bias", True, "attention_bias"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("hidden_act", "gelu", "hidden_act"),
    ("use_sliding_window", False, "use_sliding_window"),
    ("max_window_layers", 2, "max_window_layers"),
    ("layer_types", ["sliding_attention"] * 4, "window layers alone"),
    ("rope_parameters", dict(ROPE, sliding_attention={
        "rope_type": "yarn", "rope_theta": 10000}), "sliding_attention"),
    ("rope_parameters", dict(ROPE, full_attention={
        "rope_type": "llama3", "rope_theta": 10000}), "full_attention"),
    ("rope_parameters", dict(ROPE, sliding_attention={
        "rope_type": "default", "rope_theta": 5000}), "rope_theta"),
], ids=["dense-ffn", "layer-kind", "tied", "bias", "norm_topk", "act",
        "use_sliding_window", "max_window_layers", "all-window",
        "window-yarn", "full-llama3", "two-thetas"])
def test_spec_refuses_what_the_block_does_not_compute(key, value, match):
    with pytest.raises(ValueError, match=match):
        mellum_spec(dict(CONFIG, **{key: value}))


@pytest.mark.parametrize("kw, match", [
    (dict(attention="mla", kv_lora_rank=8, qk_rope_head_dim=4,
          qk_nope_head_dim=4, v_head_dim=4, q_lora_rank=8),
     "latent attention"),
    (dict(index_heads=2, index_dim=8, index_topk=4), "an indexer"),
    (dict(eva_window=16, eva_chunk=4), "EVA windows"),
    (dict(attn_gate=True), "an output gate"),
    (dict(rotary_dim=8), "partial RoPE"),
    (dict(mixers=("attention", "attention", "attention", "mamba"),
          d_inner=8, d_state=4, d_conv=2, dt_rank=2), "a lane state"),
    (dict(attn_kinds=("full", "window", "ring", "full")), "attn_kinds"),
    (dict(window=0), "window"),
    (dict(attn_kinds=()), "attn_kinds"),
], ids=["mla", "indexer", "eva", "gate", "rotary", "mamba", "kind-name",
        "no-window", "no-kinds"])
def test_model_spec_refuses_window_layers_beside_what_it_cannot_serve(
        kw, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(mellum_spec(CONFIG), **kw)


def test_yarn_needs_window_layers_or_latent_attention_and_a_factor_needs_yarn():
    sp = mellum_spec(CONFIG)
    with pytest.raises(ValueError, match="rope_scaling"):
        dataclasses.replace(sp, window=0, attn_kinds=())
    with pytest.raises(ValueError, match="rope_factor"):
        dataclasses.replace(sp, rope_scaling=())
    # all layers full and no YaRN: the plain GQA decoder, ONE group
    plain = mellum_spec(dict(
        CONFIG, layer_types=["full_attention"] * 4, rope_parameters=dict(
            ROPE, full_attention={"rope_type": "default",
                                  "rope_theta": 10000})))
    assert (plain.window, plain.attn_kinds, plain.page_groups) == (
        0, (), (("full", 4),))


def test_rope_tables_are_the_references_by_layer_kind(reference):
    """YaRN's table on the full layers and ``theta^(-2j / d)`` on the window
    layers, the program's against the reference's, at the tiny and at the
    published sizes; YaRN's ramp by hand at the published ones."""
    for config in (CONFIG, PUBLISHED):
        sp, hy = mellum_spec(config), reference.hyper_of(config)
        np.testing.assert_allclose(sp.rope_inv_freq(),
                                   np.asarray(hy["full_inv"]), rtol=1e-6)
        d = sp.head_dim
        plain = sp.rope_theta ** (-np.arange(0, d, 2) / d)
        np.testing.assert_allclose(np.asarray(hy["window_inv"]), plain,
                                   rtol=1e-6)
        assert hy["full_factor"] == sp.rope_factor
    inv = mellum_spec(PUBLISHED).rope_inv_freq()
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    # c(32) = 128 ln(8192 / 64 pi) / (2 ln 5e5) = 18.08, c(1) = 34.99: pairs
    # 0..18 keep their frequency, pairs 35.. have it divided by 16
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert ((inv[19:35] < plain[19:35]) & (inv[19:35] > plain[19:35] / 16)
            ).all()
    assert abs(0.1 * np.log(16) + 1 - 1.2772588722239782) < 1e-12


def test_plain_rope_on_the_full_layers_fails(model, reference):
    """The fault ISSUE 56 lists: the full layer turned by ``theta^(-2j /
    d)`` and no factor is another model."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    toks = np.random.default_rng(1).integers(0, VOCAB, 80).tolist()
    want = reference.last_logits(params, toks, 4, **hyper)
    got = reference.last_logits(params, toks, 4, **dict(
        hyper, full_inv=hyper["window_inv"], full_factor=1.0))
    assert np.abs(got - want).max() > 1e-2


# ------------------------------------------------- the mask's edge, by hand --

def _dense(q, k, v, qpos, window):
    """softmax over the keys ``qpos - window < j <= qpos``: ``q (M, H, D)``,
    ``k``, ``v (T, G, D)``, float64."""
    m, h, d = q.shape
    rep = h // k.shape[1]
    kk, vv = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
    s = np.einsum("mhd,thd->hmt", q, kk) / np.sqrt(d)
    j = np.arange(k.shape[0])[None, :]
    seen = j <= qpos[:, None]
    if window:
        seen &= j > qpos[:, None] - window
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hmt,thd->mhd", p, vv)


def _paged(k, v, lanes, max_pages, scramble):
    """``(pool (1, P, 2, S, G * D), tables)`` that hold ``k``, ``v (B, T, G,
    D)``: ascending pages, or a permutation of them."""
    b, t, g, d = k.shape
    n = lanes * max_pages
    ids = 1 + (np.random.default_rng(7).permutation(n) if scramble
               else np.arange(n))
    tables = ids.reshape(lanes, max_pages).astype(np.int32)
    pool = np.zeros((1, n + 1, 2, PAGE, g * d), np.float32)
    for lane in range(b):
        for pg in range(-(-t // PAGE)):
            rows = slice(pg * PAGE, min((pg + 1) * PAGE, t))
            n_r = rows.stop - rows.start
            pool[0, tables[lane, pg], 0, :n_r] = k[lane, rows].reshape(n_r, -1)
            pool[0, tables[lane, pg], 1, :n_r] = v[lane, rows].reshape(n_r, -1)
    return jnp.asarray(pool), jnp.asarray(tables)


@pytest.mark.parametrize("scramble", [False, True], ids=["runs", "scattered"])
@pytest.mark.parametrize("m", [1, 16], ids=["decode-row", "chunk-rows"])
def test_the_masks_edge_in_the_gather_form_and_in_the_kernels(m, scramble):
    """Key ``i - WINDOW`` is not seen and key ``i - WINDOW + 1`` is, row by
    row, by :func:`_gather_attend` and by both ragged kernels under the
    interpreter, on lanes under the window, at it and several blocks past
    it; entries of the table UNDER the walk's first block may be any id."""
    rng = np.random.default_rng(m)
    lanes, max_pages, h, g, d = 4, 20, 4, 2, 16
    kv_lens = np.asarray([10, WINDOW, WINDOW + 1, 117]) + m - 1
    t = int(kv_lens.max())
    k = rng.normal(size=(lanes, t, g, d)).astype(np.float32)
    v = rng.normal(size=(lanes, t, g, d)).astype(np.float32)
    q = rng.normal(size=(lanes, m, h, d)).astype(np.float32)
    qpos = (kv_lens - m)[:, None] + np.arange(m)[None, :]
    want = np.stack([_dense(q[b], k[b, :kv_lens[b]], v[b, :kv_lens[b]],
                            qpos[b], WINDOW) for b in range(lanes)])
    pool, tables = _paged(k, v, lanes, max_pages, scramble)
    # what lies wholly under a lane's first walked block is not the lane's:
    # the scratch page, as the scheduler leaves a returned block's entries
    first_block = np.maximum(kv_lens - m - WINDOW + 1, 0) // BLOCK_ROWS
    held = np.asarray(tables).copy()
    for b in range(lanes):
        held[b, :first_block[b] * (BLOCK_ROWS // PAGE)] = 0
    assert (held[3] == 0).sum() >= 8
    got = {
        "gather": _gather_attend(
            jnp.asarray(q), pool[0, :, 0], pool[0, :, 1], i32(held),
            i32(qpos), jnp.float32, WINDOW).reshape(lanes, m, h, d),
        "kernel": ra.ragged_paged_attention(
            jnp.asarray(q), pool, 0, i32(held), i32(np.full(lanes, m)),
            i32(kv_lens), interpret=True, window=WINDOW)}
    for name, out in got.items():
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-5,
                                   err_msg=name)
    # the edge itself, on the long lane's last row: a huge value at the
    # first key behind the window moves nothing, at the window's oldest key
    # it does
    last = int(kv_lens[3]) - 1
    for at, moves in ((last - WINDOW, False), (last - WINDOW + 1, True)):
        poked = np.asarray(pool).copy()
        poked[0, held[3, at // PAGE] or np.asarray(tables)[3, at // PAGE], 1,
              at % PAGE] += 100.0
        out = ra.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(poked), 0, i32(held),
            i32(np.full(lanes, m)), i32(kv_lens), interpret=True,
            window=WINDOW)
        diff = np.abs(np.asarray(out)[3, -1] - want[3, -1]).max()
        assert (diff > 1e-2) == moves, (at, diff)
    # window 0 on the same store is the full walk it always was
    full = ra.ragged_paged_attention(
        jnp.asarray(q), pool, 0, tables, i32(np.full(lanes, m)), i32(kv_lens),
        interpret=True)
    want0 = np.stack([_dense(q[b], k[b, :kv_lens[b]], v[b, :kv_lens[b]],
                             qpos[b], 0) for b in range(lanes)])
    np.testing.assert_allclose(np.asarray(full), want0, atol=2e-5)


# ------------------------------------- the step programs against the reference --

def _stores(spec, lanes, max_pages):
    """``((full, window) arrays, tables, wtables)``: every lane its own
    ascending pages in both groups."""
    pools = [PagedKVPool(n_pages=1 + lanes * max_pages, page_size=PAGE,
                         n_layers=n, n_heads=spec.n_kv_heads,
                         head_dim=spec.head_dim, dtype=jnp.float32)
             for _name, n in spec.page_groups]
    tables = 1 + np.arange(lanes * max_pages).reshape(lanes, max_pages)
    return (pools[0].kv, pools[1].kv), i32(tables), i32(tables[::-1].copy())


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_rounds_decode_rows_and_a_block_of_two_against_the_reference(
        model, reference, use_kernel):
    """Two lanes by hand: lane 0's prompt of 70 in chunks of 32, 32 and 6
    (the window slides inside the second), lane 1's of 9 riding the second
    round, then decode rows inside a round and a K = 2 block behind it from
    the round's carry; every pick's logits are the reference's full
    forward's."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    lanes, max_pages = 2, 16
    kw = dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
              compute_dtype=jnp.float32, use_kernel=use_kernel, spec=spec,
              lanes=lanes, max_pages=max_pages)
    store, tables, wtables = _stores(spec, lanes, max_pages)
    rng = np.random.default_rng(2)
    a, b = rng.integers(0, VOCAB, 70).tolist(), rng.integers(0, VOCAB,
                                                            9).tolist()

    def round_(prefill, decode, lengths, **more):
        toks, row_lane, row_off, q_lens = pack_round(lanes, prefill, decode)
        kv_lens = np.asarray(lengths, np.int32) + q_lens
        kv_lens[q_lens == 0] = 0
        return mixed_step(
            jax.jit(lambda *x: paged_mixed_step(*x, **kw)), params,
            more.pop("store"), tables, toks, row_lane, row_off, q_lens,
            kv_lens, spec=spec, wtables=wtables, **more)

    def close(logits, tokens):
        want = reference.last_logits(params, tokens, 1, **hyper)[0]
        np.testing.assert_allclose(np.asarray(logits), want, atol=3e-4)

    _, _, last, store, _ = round_({0: a[:32]}, {}, [0, 0], store=store)
    close(last[0], a[:32])
    _, _, last, store, _ = round_({0: a[32:64], 1: b}, {}, [32, 0],
                                  store=store)
    close(last[0], a[:64])
    close(last[1], b)
    tok_b = int(np.argmax(np.asarray(last[1])))
    nt, _, last, carry, store, _ = round_(
        {0: a[64:]}, {1: tok_b}, [64, 9], store=store, rem=[5, 5],
        with_carry=True)
    close(last[0], a)
    close(last[1], b + [tok_b])
    seq = [a + [int(nt[0])], b + [tok_b, int(nt[1])]]
    toks, _, ems, carry, store, _ = decode_block(
        jax.jit(lambda *x: paged_decode_block(*x, k=2, **kw)), params, store,
        tables, carry, 2, fresh=False, spec=spec, wtables=wtables)
    assert ems.all()
    for lane in range(lanes):
        # step j's pick follows the sequence with the picks before it
        for j in range(2):
            got = reference.last_logits(params, seq[lane], 1, **hyper)[0]
            assert int(np.argmax(got)) == int(toks[lane, j])
            seq[lane].append(int(toks[lane, j]))
    assert [int(x) for x in np.asarray(carry[0])] == [72, 12]


# ---------------------------------------------------------- the scheduler --

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_scheduler_under_across_and_past_the_window_against_the_reference(
        model, reference, use_kernel):
    """Prompts under the window, across it inside one chunk and several
    windows long (three rounds of 64), decode rows riding the rounds, K = 2
    blocks and a re-admission into a lane and blocks another request left:
    every emitted token's log-probability is the reference's, the window
    group never held more of a lane than its bound, and got every page back."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    cb = _engine(spec, params, use_kernel=use_kernel, decode_block=2)
    held = []
    moved = cb._window_pages
    cb._window_pages = lambda req, lo, hi: (
        moved(req, lo, hi),
        held.append((len(req.wpages), req.wfirst, lo, hi)))[0]
    try:
        assert cb.use_kernel == use_kernel
        assert (cb.pool.n_layers, cb.wpool.n_layers) == (1, 3)
        assert cb._walk_pages * PAGE == BLOCK_ROWS
        # 24 keys and a round of 64 overlap 3 blocks of 32 at most, + 1
        assert cb._wlane_pages == 4 * 4
        assert cb.wpool.n_pages == 2 * cb._wlane_pages + 1
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n).tolist()
                   for n in (10, 40, 150, 33)]
        futures = [cb.submit(p, 12, logprobs=True) for p in prompts]
        for prompt, fut in zip(prompts, futures):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(params, prompt, tokens, logprobs, **hyper)
            assert got["logprob_err_max"] < 3e-4 and got["argmax_gap"] < 3e-4
        state = cb.debug_state()
        d, groups = state["dispatch"], state["pool"]["groups"]
        assert d["kinds"]["mixed"] >= 4 and d["kinds"]["decode"] > 0
        assert d["preemptions"] == 0
        # the bound: never more than the lane's share, and always the blocks
        # that overlap (lo - window, hi]
        for n, first, lo, hi in held:
            assert n <= cb._wlane_pages
            assert first * PAGE <= max(lo - WINDOW + 1, 0)
            assert (first + n) * PAGE > hi
            assert first % 4 == 0 and n % 4 == 0
        assert max(n for n, *_ in held) >= 3 * 4      # three blocks at once
        # every page came back, in both groups
        assert groups["window"]["free_pages"] == groups["window"]["n_pages"] - 1
        assert groups["full"]["free_pages"] == groups["full"]["n_pages"] - 1
        assert state["pool"]["n_pages"] == groups["full"]["n_pages"]
        assert (groups["full"]["layers"], groups["window"]["layers"]) == (1, 3)
        assert groups["window"]["page_nbytes"] == 3 * groups["full"][
            "page_nbytes"]
        w = d["window"]
        assert w["keys"] == WINDOW and w["lane_pages"] == cb._wlane_pages
        # the 150-token prompt alone passes four blocks of 32 by its end
        assert w["pages_released"] >= 16 and w["releases"] >= 3
        work = d["lane_work"]
        for kind in ("round", "decode"):
            assert 0 < work[kind]["window_keys"] <= work[kind]["keys"]
        assert work["decode"]["window_keys"] < work["decode"]["keys"]
        assert 0 < d["round_window_pairs"] < d["round_attn_pairs"]
        # the window group's blocks are one run each, every one walked
        assert groups["window"]["walk_run_blocks"] == groups["window"][
            "walk_blocks"] > 0
        assert state["pool"]["walk_blocks"] == sum(
            g["walk_blocks"] for g in groups.values())
    finally:
        cb.shutdown()


def test_window_keys_by_hand():
    """``lane_work``'s window keys: a decode row at context c attends min(c,
    window) keys; a round's segment reads the keys from its first row's
    window on; the pairs a round computes are the rows' sum."""
    spec = mellum_spec(CONFIG)
    cb = _engine(spec, init_params(spec, VOCAB, 0))
    try:
        cb._note_rows("decode", 20, 10)      # contexts 21 .. 30
        assert cb.lane_work["decode"]["window_keys"] == sum(
            min(c, WINDOW) for c in range(21, 31))
        assert cb.lane_work["decode"]["keys"] == sum(range(21, 31))
        cb._note_rows("round", 100, 16)
        assert cb.lane_work["round"]["window_keys"] == WINDOW + 16 - 1
        assert cb.lane_work["round"]["keys"] == 116
        assert cb.round_window_pairs == 16 * WINDOW
        cb._note_rows("round", 0, 30)        # a first chunk, under and past
        assert cb.lane_work["round"]["window_keys"] == WINDOW + 15 + 30
        assert cb.round_window_pairs == 16 * WINDOW + sum(
            min(c, WINDOW) for c in range(1, 31))
    finally:
        cb.shutdown()


def test_one_lane_takes_the_block_another_returned_under_a_living_stream(
        model, reference):
    """Two lanes: A decodes past block after block of its window while B,
    admitted behind A's first token, prefills in rounds that carry A's
    decode row and then decodes in the blocks A's chain runs: B's window
    table takes pages A returned WHILE A LIVES (the lowest free ids are the
    ones A gave back), a decode block and a round each chained behind a
    release, and both streams are the reference's."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    cb = _engine(spec, params, decode_block=2, prefill_chunk=16)
    freed, taken, now = {}, {}, []
    release, grant = cb.wpool.release_pages, cb.wpool.allocate_pages

    def moved(req, lo, hi, inner=cb._window_pages):
        now.append(req)             # whose reservation moves the table
        try:
            inner(req, lo, hi)
        finally:
            now.pop()

    def released(pages):
        if now:                     # (not a lane's release at its end)
            freed.setdefault(id(now[-1]), []).extend(pages)
        release(pages)

    def granted(n, after=0):
        pages = grant(n, after)
        taken.setdefault(id(now[-1]), []).extend(pages)
        return pages
    cb._window_pages = moved
    cb.wpool.release_pages, cb.wpool.allocate_pages = released, granted
    try:
        rng = np.random.default_rng(3)
        pa, pb = (rng.integers(0, VOCAB, n).tolist() for n in (90, 70))
        gate = FirstTokenGate()
        fa = cb.submit(pa, 80, logprobs=True, on_token=gate)
        req_a = cb._requests[fa]
        assert gate.wait(timeout=60)
        fb = cb.submit(pb, 40, logprobs=True)
        req_b = cb._requests[fb]
        gate.release()
        for prompt, fut in ((pa, fa), (pb, fb)):
            tokens, logprobs = fut.result(timeout=600)
            got = reference.compare(params, prompt, tokens, logprobs, **hyper)
            assert got["logprob_err_max"] < 3e-4 and got["argmax_gap"] < 3e-4
        # B took pages A had returned under its own living stream (what A
        # held at its end goes back through no reservation, and is not
        # counted here)
        assert len(freed[id(req_a)]) >= 16
        assert set(freed[id(req_a)]) & set(taken[id(req_b)])
        d = cb.debug_state()["dispatch"]
        assert d["ahead_rounds"] > 0 and d["ahead_blocks"] > 0
        assert d["window"]["releases"] >= 4
        g = cb.debug_state()["pool"]["groups"]["window"]
        assert g["free_pages"] == g["n_pages"] - 1
    finally:
        cb.shutdown()


def test_what_a_finished_stream_leaves_in_both_groups_is_the_references(
        model, reference):
    """``debug_state()["last_release"]`` names the pages of BOTH groups the
    request that ended last held: the full layer's rows at every position
    and the window layers' from the first row their table still held are
    the reference's, which is what the benchmark's ``correct`` reads."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    cb = _engine(spec, params, lanes=1)
    try:
        prompt = np.random.default_rng(4).integers(0, VOCAB, 100).tolist()
        toks, lps = cb.submit(prompt, steps=12, logprobs=True).result(
            timeout=300)
        held = cb.debug_state()["last_release"]
        length, first = held["length"], held["window_first"]
        assert length == 111 and held["lane"] == 0
        # blocks of 32: position 111 - 24 + 1 = 88 lies in block 2
        assert first * PAGE == 64 and len(held["window_pages"]) == 8

        def rows(pool, pages):
            kv = np.asarray(pool.kv[:, np.asarray(pages)])
            return np.moveaxis(kv, 2, 1).reshape(kv.shape[0], 2, -1,
                                                 kv.shape[-1])
        served = {"full": rows(cb.pool, held["pages"])[:, :, :length],
                  "window": rows(cb.wpool, held["window_pages"])[
                      :, :, :length - first * PAGE],
                  "window_start": first * PAGE}
        got = reference.token_errors(params, prompt, toks, lps,
                                     stores=served, **hyper)
        assert got["layer_kv_err"].shape == (4,)
        assert got["layer_kv_err"].max() < 1e-4
        assert max(got["kv_err"], got["kv1_err"], got["full_kv_err"]) < 1e-4
        assert got["logprob_err"].max() < 3e-4
        # a window layer's rows in the FULL group's pages would be others
        swapped = dict(served, window=served["window"][::-1])
        bad = reference.token_errors(params, prompt, toks, lps,
                                     stores=swapped, **hyper)
        assert bad["layer_kv_err"].max() > 0.1
    finally:
        cb.shutdown()


def test_a_window_layer_that_sees_every_key_fails(model, reference):
    """The fault ISSUE 56 lists first: the reference with the window taken
    away is another model from the first row past the window on, and the
    same sequence under the window is the same model."""
    spec, params = model
    hyper = reference.hyper_of(CONFIG)
    toks = np.random.default_rng(6).integers(0, VOCAB, 60).tolist()
    for n, differs in ((WINDOW, False), (60, True)):
        want = reference.last_logits(params, toks[:n], 1, **hyper)
        got = reference.last_logits(params, toks[:n], 1,
                                    **dict(hyper, window=10 ** 6))
        assert (np.abs(got - want).max() > 1e-3) == differs
    # ... and a window whose edge lies a page off
    off = reference.last_logits(params, toks, 1,
                                **dict(hyper, window=WINDOW + PAGE))
    assert np.abs(off - reference.last_logits(params, toks, 1, **hyper)
                  ).max() > 1e-3


def test_a_preempted_request_prefills_again_to_a_fresh_engines_tokens(model):
    """A high-priority arrival evicts the one lane's request mid-decode:
    both groups' pages go back, and the victim prefills again from position
    0 into blocks the other request used meanwhile, to the tokens of an
    undisturbed run."""
    spec, params = model
    prompt = np.random.default_rng(8).integers(0, VOCAB, 50).tolist()

    def fresh():
        cb = _engine(spec, params, lanes=1)
        try:
            return cb.submit(prompt, 30).result(timeout=300)
        finally:
            cb.shutdown()
    want = fresh()
    cb = _engine(spec, params, lanes=1)
    try:
        gate = FirstTokenGate()
        victim = cb.submit(prompt, 30, on_token=gate)
        assert gate.wait(timeout=60)
        other = cb.submit(prompt[:20], 4, priority=5)
        gate.release()
        assert len(other.result(timeout=300)) == 4
        assert victim.result(timeout=300) == want
        state = cb.debug_state()
        assert state["dispatch"]["preemptions"] == 1
        g = state["pool"]["groups"]["window"]
        assert g["free_pages"] == g["n_pages"] - 1
    finally:
        cb.shutdown()


@pytest.mark.parametrize("option", [
    dict(prefix_cache=True), dict(kv_offload=True),
    dict(kv_offload=True, kv_publish=True), dict(hbm=object()),
    dict(draft_params={}), dict(mesh=object()),
    dict(kv_dtype=jnp.float8_e4m3fn)],
    ids=["prefix_cache", "kv_offload", "kv_publish", "hbm", "draft_params",
         "mesh", "kv_dtype"])
def test_options_two_page_groups_do_not_carry_are_refused_by_name(
        model, option, request):
    """What hangs on "a request's pages hold its positions" (the prefix
    cache, the K+1 verify, the host tier and shipping, preemption by swap)
    is refused for a spec with two page groups, by name."""
    spec, params = model
    with pytest.raises(NotImplementedError,
                       match=request.node.callspec.id) as err:
        _engine(spec, params, **option)
    assert "window layers (a page table a layer kind" in str(err.value)


def test_a_provided_pool_is_refused_with_two_groups(model):
    spec, params = model
    pool = PagedKVPool(n_pages=9, page_size=PAGE, n_layers=1, n_heads=2,
                       head_dim=16, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="provided pool"):
        _engine(spec, params, pool=pool)


@pytest.mark.parametrize("kind", list(test_engine_plan.KINDS))
def test_the_kinds_before_this_one_keep_one_group(kind):
    """A spec whose attention layers are all of one kind gets ONE group: the
    buffer's fields, the plan and ``debug_state()["pool"]`` are what they
    were."""
    spec, vocab, d_ff, kw = test_engine_plan.KINDS[kind]
    if spec is not None:
        assert len(spec.page_groups) == 1 and spec.page_groups[0][0] == "full"
        assert not spec.window and not spec.attn_kinds
        assert all(spec.layer_window(i) == 0 for i in range(spec.n_layers))
    for program in ("tick", "block", "round"):
        names = [n for n, _t, _s in dispatch_fields(program, 2, 8)]
        assert names[0] == "tables" and "wtables" not in names
        assert [n for n, _t, _s in dispatch_fields(program, 2, 8, True)][
            :2] == ["tables", "wtables"]


# ---------------------- the kernels at the published widths, Mosaic ----

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


@pytest.mark.parametrize("rows, window", [(1, 1024), (512, 1024), (1, 0)],
                         ids=["decode-window", "chunk-512-window",
                              "decode-full"])
def test_mosaic_compiles_the_walks_at_the_cells_widths(one_chip, monkeypatch,
                                                       rows, window):
    """The one-row and the rows kernel with the window's lower bound, and
    the full walk beside them, at ``mellum2-l8``'s shapes (32 lanes, 32
    heads on 4 KV heads of 128, pages of 16 rows, tables of 2,048), compiled
    for a described v5e: no chip, no run."""
    monkeypatch.setattr(ra, "_TARGET_BLOCK_ROWS", 256)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    lanes, max_pages = 32, 2048
    layers, pages = (6, 32 * 112 + 1) if window else (2, 32769)
    compiled = jax.jit(
        lambda *a: ra._ragged_attn(*a, False, window=window)).lower(
        shape(lanes, rows, 32, 128), shape(layers, pages, 2, 16, 512),
        shape(1, dtype=jnp.int32), shape(lanes, max_pages, dtype=jnp.int32),
        shape(lanes, dtype=jnp.int32), shape(lanes, dtype=jnp.int32)
    ).compile()
    assert ("ragged_paged_decode" if rows == 1
            else "ragged_paged_attention") in compiled.as_text()
