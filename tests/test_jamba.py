"""Mamba layers on a per-lane recurrent state beside the page store.

A tiny hybrid that keeps every ratio of ``jamba`` (attention at offset 1 of
period 3, so Mamba layers before, between and after attention layers; 4
query heads on 1 KV head without positional encoding; ``d_inner`` twice the
hidden size, a 4-tap convolution, a dt rank narrower than the state; a tied
head), held to the benchmark's plain float32 reference
(``perf/reference/jamba.py``: one sequential scan over the tokens, full
attention, nothing imported from the program).
"""

import importlib.util
import os
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers_engine import FirstTokenGate
from helpers_steps import decode_block, mixed_step
from tpulab.engine.kv_pool import (LaneStateStore, PagedKVPool,
                                   lane_state_shapes)
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import (_mamba_mixer, pack_round,
                                       paged_decode_block, paged_decode_step,
                                       paged_mixed_step, paged_ragged_forward)
from tpulab.models.spec import init_params, jamba_spec
from tpulab.ops.selective_scan import row_flags, selective_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, D_FF, LANES, PAGE = 97, 96, 4, 8
CONFIG = {
    "model_type": "jamba", "hidden_size": 64, "intermediate_size": D_FF,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "num_hidden_layers": 5, "attn_layer_period": 3, "attn_layer_offset": 1,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 6,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_experts": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": VOCAB,
}
i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "jamba.py")
    spec = importlib.util.spec_from_file_location("ref_jamba", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    spec = jamba_spec(CONFIG)
    # weights large enough that every term of the block shows in a logit
    return spec, init_params(spec, VOCAB, D_FF, seed=3, scale=0.1)


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


def _round(spec, params, store, tables, prefill, decode, lengths,
           use_kernel=False):
    """One ``paged_mixed_step``: ``prefill`` {lane: chunk}, ``decode`` {lane:
    token}, ``lengths`` the lanes' positions before it.  Returns ``(last
    logits (LANES, vocab), store)``."""
    toks, row_lane, row_off, q_lens = pack_round(LANES, prefill, decode)
    kv_lens = np.asarray(lengths, np.int32) + q_lens
    kv_lens[q_lens == 0] = 0          # as the scheduler leaves idle lanes
    _nt, _lp, last, store = mixed_step(
        partial(paged_mixed_step, lanes=LANES, max_pages=4,
                **_kw(spec, use_kernel)),
        params, store, tables, toks, row_lane, row_off, q_lens, kv_lens)
    return np.asarray(last), store


def _lane_state(store, lane):
    ssm, conv = store[1]
    return np.asarray(ssm[:, lane]), np.asarray(conv[:, :, lane])


def test_spec_reads_the_published_keys():
    spec = jamba_spec(CONFIG)
    assert spec.mixers == ("mamba", "attention", "mamba", "mamba",
                           "attention")
    assert spec.mamba_layers == (0, 2, 3) and spec.attention_layers == (1, 4)
    assert [spec.store_layer(i) for i in range(5)] == [0, 0, 1, 2, 1]
    assert (spec.d_inner, spec.d_state, spec.d_conv, spec.dt_rank) == (
        128, 8, 4, 6)
    assert spec.cache_entry == "kv" and spec.rope_theta is None
    hash(spec)     # it keys the jit memo
    for key, value in (("num_experts", 16), ("sliding_window", 4096),
                       ("mamba_proj_bias", True)):
        with pytest.raises(ValueError, match=key):
            jamba_spec(dict(CONFIG, **{key: value}))


def test_the_published_rows_layer_order_and_state_bytes():
    """At AI21-Jamba2-3B's published keys: attention on layers 7 and 21,
    9,318,400 B of state a lane, 1,024 B of pages a token."""
    import json
    with open(os.path.join(ROOT, "perf", "configs", "jamba2-3b.json")) as f:
        spec = jamba_spec(json.load(f))
    assert spec.attention_layers == (7, 21) and len(spec.mamba_layers) == 26
    assert sum(int(np.prod(shape)) * dtype.itemsize for shape, dtype in
               lane_state_shapes(spec, 32, jnp.bfloat16)) // 32 == 9_318_400
    assert 2 * 2 * spec.n_kv_heads * spec.head_dim * 2 == 1024


def test_ssm_leaves_follow_the_published_initialisation(model):
    spec, params = model
    m = params["layer0"]["mamba"]
    np.testing.assert_allclose(np.exp(np.asarray(m["a_log"]))[:, 5],
                               np.arange(1, 9), rtol=1e-6)
    assert (np.asarray(m["d"]) == 1).all()
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert "lm_head" not in params and "mamba" not in params["layer1"]


def test_mixer_matches_the_plain_references_layer(model, reference):
    """One Mamba layer's mixer over a packed round of one 13-token segment
    from zeros, against the reference's sequential scan."""
    spec, params = model
    p = params["layer2"]["mamba"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((1, 13, 64)),
                    jnp.float32)
    store, _ = _fresh(spec, junk=True)
    _toks, row_lane, row_off, q_lens = pack_round(
        LANES, {2: np.zeros(13, np.int32)}, {})
    h_rows = jnp.zeros((1, len(row_lane), 64), jnp.float32).at[:, :13].set(h)
    spread = jnp.zeros((LANES * 16,), jnp.int32).at[
        2 * 16 + jnp.arange(13)].set(jnp.arange(13))
    seg = dict(q_lens=i32(q_lens), kv_lens=i32(q_lens), use_kernel=False,
               rows=(spread, None, None), row_seg=(i32(row_lane),
                                                   i32(row_off)))
    out, (ssm, conv) = _mamba_mixer(spec, p, 1, h_rows, None, None, store[1],
                                    seg, jnp.float32)
    want = reference.mamba_mixer(h[0], p, eps=spec.rms_eps)
    np.testing.assert_allclose(np.asarray(out)[0, :13], want, rtol=2e-5,
                               atol=2e-5)
    # only layer 1 of the store and only lane 2 of it were written
    assert (np.asarray(ssm)[[0, 2]] == 3).all()
    assert (np.asarray(ssm)[1, [0, 1, 3]] == 3).all()
    assert not (np.asarray(ssm)[1, 2] == 3).any()


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_one_chunk_uneven_chunks_and_token_by_token_agree(model, reference,
                                                          use_kernel):
    """A 21-token prompt through mixed rounds in one chunk, in chunks of 8,
    3, 1 and 9, and token by token through decode steps: the same logits at
    the last position, the same lane state, and the reference's logits."""
    spec, params = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, 21)
    want = reference.last_logits(params, tokens.tolist(), 1,
                                 **reference.hyper_of(CONFIG))[0]
    outs = []
    for sizes in ([21], [8, 3, 1, 9]):
        store, tables = _fresh(spec, junk=True)
        at = 0
        for n in sizes:
            lengths = [0, at, 0, 0]
            last, store = _round(spec, params, store, tables,
                                 {1: tokens[at:at + n]}, {}, lengths,
                                 use_kernel)
            at += n
        outs.append((last[1], _lane_state(store, 1)))
    store, tables = _fresh(spec, junk=True)
    for pos, tok in enumerate(tokens):
        logits, store = paged_decode_step(
            params, store, tables, i32([0, pos, 0, 0]), i32([0, tok, 0, 0]),
            jnp.asarray([False, True, False, False]), **_kw(spec, use_kernel))
    outs.append((np.asarray(logits)[1], _lane_state(store, 1)))
    for logits, (ssm, conv) in outs:
        np.testing.assert_allclose(logits, want, rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(ssm, outs[0][1][0], rtol=3e-5, atol=1e-5)
        np.testing.assert_allclose(conv, outs[0][1][1], rtol=3e-5, atol=1e-5)
    # the lanes that ran nothing still hold what they held
    assert (_lane_state(store, 0)[0] == 3).all()
    assert (_lane_state(store, 3)[1] == 3).all()


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_a_round_of_several_lanes_is_each_lane_alone(model, use_kernel):
    """Two lanes' chunks (one a first chunk, one a later chunk) and two
    other lanes' decode rows in ONE packed round give, lane for lane, the
    logits and the state of four rounds that carry one lane each."""
    spec, params = model
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, VOCAB, n) for n in (14, 9, 12, 7)]

    def warm(store, tables):
        """Lanes 0, 1 and 3 have a past: 9, 8 and 6 tokens."""
        return _round(spec, params, store, tables,
                      {0: seqs[0][:9], 1: seqs[1][:8], 3: seqs[3][:6]}, {},
                      [0, 0, 0, 0], use_kernel)[1]

    lengths = [9, 8, 0, 6]
    prefill = {0: seqs[0][9:14], 2: seqs[2]}        # a later and a first chunk
    decode = {1: int(seqs[1][8]), 3: int(seqs[3][6])}
    store, tables = _fresh(spec, junk=True)
    together, store = _round(spec, params, warm(store, tables), tables,
                             prefill, decode, lengths, use_kernel)
    for lane in range(LANES):
        alone, tables = _fresh(spec, junk=True)
        last, alone = _round(
            spec, params, warm(alone, tables), tables,
            {k: v for k, v in prefill.items() if k == lane},
            {k: v for k, v in decode.items() if k == lane}, lengths,
            use_kernel)
        np.testing.assert_allclose(together[lane], last[lane], rtol=2e-5,
                                   atol=2e-5)
        for a, b in zip(_lane_state(store, lane), _lane_state(alone, lane)):
            np.testing.assert_allclose(a, b, rtol=3e-5, atol=1e-5)


def test_a_lane_that_stops_inside_a_block_holds_its_state(model):
    """One block of K = 8 against eight blocks of K = 1: lane 1 has three
    steps left, so it is dead for five steps of the block and its state is
    what the third step left; lane 2 never ran and keeps what it held."""
    spec, params = model
    rng = np.random.default_rng(6)
    prompts = {0: rng.integers(0, VOCAB, 5), 1: rng.integers(0, VOCAB, 7)}

    def run(k):
        store, tables = _fresh(spec, junk=True)
        last, store = _round(spec, params, store, tables, prompts, {},
                             [0, 0, 0, 0])
        carry = ([5, 7, 0, 0], last.argmax(-1), [True, True, False, False],
                 [8, 3, 0, 0])
        block = partial(paged_decode_block, lanes=LANES, max_pages=4, k=k,
                        **_kw(spec))
        out = []
        for i in range(8 // k):
            # the first block takes its state from the buffer, the ones
            # behind it from the carry the block before returned
            toks, _lps, ems, carry, store = decode_block(
                block, params, store, tables, carry, k, fresh=i == 0)
            out.append(np.where(ems, toks, -1))
        return np.concatenate(out, axis=1), store, carry

    toks8, store8, carry8 = run(8)
    toks1, store1, carry1 = run(1)
    np.testing.assert_array_equal(toks8, toks1)
    assert (toks8[1, 3:] == -1).all() and (toks8[0] >= 0).all()
    np.testing.assert_array_equal(np.asarray(carry8[0]), [13, 10, 0, 0])
    for lane in range(LANES):
        for a, b in zip(_lane_state(store8, lane), _lane_state(store1, lane)):
            np.testing.assert_allclose(a, b, rtol=3e-5, atol=1e-5)
    assert (_lane_state(store8, 2)[0] == 3).all()


def test_the_padded_form_refuses_mamba_layers(model):
    spec, params = model
    store, tables = _fresh(spec)
    with pytest.raises(NotImplementedError, match="padded"):
        paged_ragged_forward(params, store, tables,
                             jnp.zeros((LANES, 4), jnp.int32),
                             i32([4, 0, 0, 0]), i32([4, 0, 0, 0]),
                             **_kw(spec))


def test_kernel_in_interpret_mode_matches_the_scan_form():
    """The Pallas kernel (interpreter) against the ``lax.scan`` form on a
    round with a first chunk, a later chunk, decode rows, rows without a
    token and a row count that is no whole tile."""
    rng = np.random.default_rng(0)
    t, din, n, lanes = 21, 64, 8, 4
    row_lane, row_off = np.full(t, -1, np.int32), np.zeros(t, np.int32)
    row_lane[0:7], row_off[0:7] = 2, np.arange(7)       # from position 0
    row_lane[7:12], row_off[7:12] = 0, np.arange(5)     # from position 9
    row_lane[17], row_lane[19] = 1, 3                   # decode rows
    flags = row_flags(i32(row_lane), i32(row_off), i32([5, 1, 7, 1]),
                      i32([14, 30, 7, 1]))
    np.testing.assert_array_equal(np.asarray(flags)[[0, 6, 7, 11, 12, 17,
                                                     19]],
                                  [7, 9, 3, 9, 0, 11, 15])
    f32 = lambda x: jnp.asarray(x, jnp.float32)         # noqa: E731
    args = (f32(rng.standard_normal((t, din))),
            f32(rng.uniform(1e-3, 1e-1, (t, din))),
            f32(rng.standard_normal((t, n))), f32(rng.standard_normal((t, n))),
            -f32(rng.uniform(1, 8, (n, din))), jnp.ones((din,), jnp.float32),
            f32(rng.standard_normal((3, lanes, n, din))), 1, i32(row_lane),
            flags)
    y0, s0 = selective_scan(*args, use_kernel=False)
    y1, s1 = selective_scan(*args, use_kernel=True)
    live = row_lane >= 0
    np.testing.assert_allclose(np.asarray(y1)[live], np.asarray(y0)[live],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-6,
                               atol=1e-6)
    # layers 0 and 2 never moved; every lane of layer 1 ended a segment
    np.testing.assert_array_equal(np.asarray(s0)[[0, 2]],
                                  np.asarray(args[6])[[0, 2]])
    assert (np.asarray(s0)[1] != np.asarray(args[6])[1]).any(axis=(1, 2)).all()


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
        assert cb.pool.n_layers == 2 and cb.pool.bytes_per_token == 2 * 2 * 16 * 4
        prompt = np.random.default_rng(1).integers(0, VOCAB, 21).tolist()
        toks, lps = cb.submit(prompt, steps=10, logprobs=True).result(
            timeout=300)
        got = reference.compare(params, prompt, toks, lps,
                                **reference.hyper_of(CONFIG))
        assert got["logprob_err"] < 5e-5 and got["argmax_gap"] == 0
        state = cb.debug_state()["state"]
        assert state["kind"] == "mamba" and state["lanes"] == 3
        assert state["zero_starts"] == 1
        assert state["bytes_per_lane"] == 3 * (8 * 128 * 4 + 3 * 128 * 4)
        assert state["hbm_bytes"] == 3 * state["bytes_per_lane"]
        assert cb.debug_state()["dispatch"]["kinds"]["mixed"] == 3
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
    """Blocks enqueued ahead run on the state their predecessor returned:
    the same streams as an engine whose chain is held back."""
    spec, params = model
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (9, 13, 6)]

    def run(chain):
        if not chain:
            monkeypatch.setattr(ContinuousBatcher, "_chain_block",
                                lambda self, stash, jnp, ahead: (None, "k1"))
        cb = _engine(spec, params)
        try:
            futs = [cb.submit(p, 24) for p in prompts]
            out = [f.result(timeout=300) for f in futs]
            return out, cb.ahead_blocks
        finally:
            cb.shutdown()
            monkeypatch.undo()

    chained, ahead = run(True)
    plain, none_ahead = run(False)
    assert ahead > 0 and none_ahead == 0
    assert chained == plain
    assert chained == [_fresh_tokens(spec, params, p, 24) for p in prompts]


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
    dict(prefix_cache=True), dict(kv_offload=True),
    dict(kv_offload=True, kv_publish=True), dict(hbm=object()),
    dict(draft_params={"layer0": {}}), dict(mesh=object()),
    dict(kv_dtype=jnp.float16)],
    ids=["prefix_cache", "kv_offload", "kv_publish", "hbm", "draft_params",
         "mesh", "kv_dtype"])
def test_options_the_lane_state_does_not_carry_are_refused_by_name(
        model, option, request):
    spec, params = model
    name = request.node.callspec.id
    with pytest.raises(NotImplementedError, match=name):
        _engine(spec, params, **option)


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


@pytest.mark.parametrize("rows", [544, 288, 33], ids=["M512", "M256", "M1"])
def test_mosaic_compiles_the_scan_kernel_at_the_published_widths(one_chip,
                                                                 rows):
    """AI21-Jamba2-3B's widths, the cell's 32 lanes and 26 layers of state,
    a full round (512 + 32 rows; 256 + 32 before PR 42) and the smallest
    (1 + 32, no whole tile):
    what the interpreter cannot refuse, Mosaic can (tiling, VMEM)."""
    from tpulab.ops.selective_scan import _scan_call
    d_inner, n, lanes, layers = 5120, 16, 32, 26

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    i32s = lambda *dims: shape(*dims, dtype=jnp.int32)      # noqa: E731
    compiled = _scan_call.lower(
        shape(rows, d_inner), shape(rows, d_inner), shape(rows, n),
        shape(rows, n), shape(n, d_inner), shape(d_inner),
        shape(layers, lanes, n, d_inner), i32s(1), i32s(rows), i32s(rows),
        interpret=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
