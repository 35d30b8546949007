"""Mamba-2 layers on a head-shaped lane state, layers of ONE sublayer, relu2
experts of a width that is not whole lanes.

A tiny hybrid that keeps every feature of ``nemotron_h`` (the letters ``M*``
side by side, so that no (mixer, FFN) pairing of the pattern exists; 4 heads
of 8 channels on 2 groups of a 16-wide state; a 4-tap convolution over ``[x |
B | C]`` together; a chunk of 8 rows, so that a round of 16 crosses a chunk
boundary and a prompt of 37 two round boundaries; 4 query heads on 2 KV heads
without positional rotation; top-3 of 16 sigmoid-routed relu2 experts of
width 24, served at 128, and a shared one; an untied head), held to the
benchmark's plain float32 reference (``perf/reference/nemotron_h.py``: the
sequential recurrence, full attention, a loop over the held experts, nothing
imported from the program).
"""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers_engine import FirstTokenGate
from tpulab.engine.kv_pool import lane_state_shapes
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.plan import plan_engine
from tpulab.models.spec import (init_params, nemotron_h_layout,
                                nemotron_h_spec)
from tpulab.ops import ssd
from tpulab.ops.selective_scan import row_flags
from tpulab.parallel.moe import routed_ffn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(ROOT, "tests", "data", "step_programs_pr61.json")
GOLDEN_PR60 = os.path.join(ROOT, "tests", "data", "step_programs_pr60.json")
GOLDEN_PR59 = os.path.join(ROOT, "tests", "data", "step_programs_pr59.json")
VOCAB, PAGE = 97, 8
CONFIG = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_hidden_layers": 8,
    "hybrid_override_pattern": "MEM*EM*E", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8, "expand": 2, "n_routed_experts": 16,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "use_conv_bias": True,
    "tie_word_embeddings": False, "vocab_size": VOCAB,
}


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perf", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("ref_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    spec = nemotron_h_spec(CONFIG)
    # weights large enough that every term of a block shows in a logit
    return spec, init_params(spec, VOCAB, 0, seed=3, scale=0.1)


def _engine(spec, params, **kw):
    kw = dict(dict(lanes=2, max_len=64, page_size=PAGE,
                   compute_dtype=jnp.float32, prefill_chunk=16), **kw)
    return ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                             **kw)


# ------------------------------------------------------------- the spec ----

def test_spec_reads_the_published_keys_and_the_pattern():
    spec = nemotron_h_spec(CONFIG)
    assert spec.mixers == ("mamba2", "none", "mamba2", "attention", "none",
                           "mamba2", "attention", "none")
    assert spec.layer_kinds == ("none", "moe", "none", "none", "moe", "none",
                                "none", "moe")
    assert spec.state_kind == "mamba2" and spec.state_layers == (0, 2, 5)
    assert spec.attention_layers == (3, 6) and spec.moe_layers == (1, 4, 7)
    assert [spec.store_layer(i) for i in (0, 2, 5, 3, 6)] == [0, 1, 2, 0, 1]
    assert (spec.m2_heads, spec.m2_head_dim, spec.m2_groups, spec.m2_state,
            spec.m2_chunk, spec.d_conv, spec.m2_conv_dim) == (
                4, 8, 2, 16, 8, 4, 32 + 2 * 2 * 16)
    assert spec.rope_theta is None and spec.rms_eps == 1e-5
    assert (spec.router, spec.expert_act, spec.routed_scale, spec.norm_topk,
            spec.n_shared) == ("sigmoid_bias", "relu2", 2.5, True, 2)
    # 24 is not whole lanes: the served experts are padded to 128
    assert (spec.moe_ff, spec.moe_ff_pad, spec.moe_ff_served) == (24, 104,
                                                                   128)
    assert spec.cache_entry == "kv" and not spec.mamba_layers
    hash(spec)     # it keys the jit memo
    share = nemotron_h_spec(CONFIG, first=8, held=2)
    assert (share.n_experts, share.experts_held, share.expert_first) == (
        16, 2, 8)
    # the published row: 23 / 23 / 6 over 52, 1,856 served at 1,920
    row = dict(CONFIG, num_hidden_layers=52, moe_intermediate_size=1856,
               moe_shared_expert_intermediate_size=3712,
               hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                       "EMEMEMEM*EMEMEMEME")
    full = nemotron_h_spec(row)
    assert (len(full.state_layers), len(full.attention_layers),
            len(full.moe_layers), full.moe_ff_served) == (23, 6, 23, 1920)
    # a configuration cut in depth keeps the pattern whole: its first letters
    assert nemotron_h_spec(dict(row, num_hidden_layers=43)).mixers[-1] == (
        "attention")


@pytest.mark.parametrize("change, message", [
    (dict(hybrid_override_pattern="MEM-EM*E"), "letters"),
    (dict(hybrid_override_pattern="MEM*"), "letters"),
    (dict(n_group=2), "group-limited"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mlp_hidden_act="silu"), "relu2"),
    (dict(hybrid_override_pattern="MEMEEMME"), "GQA attention"),
], ids=["dense-letter", "short-pattern", "groups", "bias", "act",
        "no-attention"])
def test_spec_refuses_what_the_layer_block_does_not_compute(change, message):
    with pytest.raises(ValueError, match=message):
        nemotron_h_spec(dict(CONFIG, **change))


def test_a_mamba2_state_is_refused_beside_the_other_kinds_by_name():
    import dataclasses
    spec = nemotron_h_spec(CONFIG)
    for change, name in ((dict(attention="mla"), "latent attention"),
                         (dict(hc_mult=2, hc_sinkhorn_iters=2),
                          "hyper-connections"),
                         (dict(res_scale=True), "residual scaling")):
        with pytest.raises(ValueError, match=name):
            dataclasses.replace(spec, **change)
    with pytest.raises(ValueError, match="neither"):
        dataclasses.replace(spec, layer_kinds=("none",) * 8)
    with pytest.raises(ValueError, match="relu2 experts alone"):
        dataclasses.replace(spec, expert_act="swiglu")


def test_lane_state_shapes_and_the_plan_of_the_fourth_kind(model):
    spec, _params = model
    ssm, conv = lane_state_shapes(spec, 5, jnp.bfloat16)
    assert ssm == ((3, 5, 4, 8, 16), np.dtype(np.float32))
    assert conv == ((3, 3, 5, 96), np.dtype(jnp.bfloat16))
    kw = dict(spec=spec, n_heads=4, n_layers=8, n_kv_heads=None,
              rope_theta=None, d_model=64, vocab=VOCAB, lanes=2, max_len=64,
              page_size=PAGE, prefill_chunk=None, use_kernel=False,
              compute_dtype=jnp.float32, kv_dtype=None, round_ceiling=512,
              kernel_auto_min_ctx=8192)
    plan = plan_engine(**kw)
    assert plan.state_kind == "mamba2" and plan.pool_layers == 2
    assert plan.state_rule == {"decode": "xla", "round": "xla"}
    # with the kernels the one-token rule is one; the chunked form stays XLA
    assert plan_engine(**dict(kw, use_kernel=True)).state_rule == {
        "decode": "kernel", "round": "xla"}
    assert (plan.n_kv, plan.head_dim) == (2, 16)
    for option in (dict(prefix_cache=True), dict(kv_offload=True),
                   dict(mesh=object()), dict(kv_publish=True),
                   dict(draft_params={})):
        with pytest.raises(NotImplementedError) as err:
            plan_engine(**dict(kw, **option))
        assert "mamba2 layers" in str(err.value)
        assert "moe FFNs" in str(err.value) and "none" not in str(err.value)


# ------------------------------------------- the recurrence's three forms ----

def _segments(rng, t, segs, before, lanes=5, h=4, p=8, g=2, n=16, layers=2):
    """A round's rows: ``segs`` ``(lane, rows)`` packed end to end, ``before``
    the positions a lane held already; junk in every slot."""
    row_lane = -np.ones(t, np.int32)
    row_off = np.zeros(t, np.int32)
    q_lens, kv_lens = np.zeros(lanes, np.int32), np.zeros(lanes, np.int32)
    r = 0
    for lane, rows in segs:
        row_lane[r:r + rows], row_off[r:r + rows] = lane, np.arange(rows)
        q_lens[lane], kv_lens[lane] = rows, rows + before.get(lane, 0)
        r += rows
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731
    flags = row_flags(jnp.asarray(row_lane), jnp.asarray(row_off),
                      jnp.asarray(q_lens), jnp.asarray(kv_lens))
    return dict(
        x=f32(rng.normal(size=(t, h, p))),
        dt=f32(rng.uniform(0.01, 0.5, size=(t, h))),
        a=-f32(rng.uniform(1, 8, size=(h,))),
        b=f32(rng.normal(size=(t, g, n))), c=f32(rng.normal(size=(t, g, n))),
        d=f32(rng.normal(size=(h,))),
        states=f32(rng.normal(size=(layers, lanes, h, p, n)))), (
            jnp.asarray(row_lane), flags)


@pytest.mark.parametrize("t, segs, before, chunk", [
    (32, [(0, 32)], {0: 5}, 8),
    (32, [(1, 5), (3, 20), (0, 4)], {3: 7}, 8),
    (32, [(1, 5), (3, 20), (0, 4)], {}, 8),
    (16, [(2, 3)], {2: 1}, 128),
    (24, [(2, 9), (4, 1), (0, 8), (1, 2)], {4: 3, 1: 9}, 8),
    (12, [(0, 12)], {}, 8),
], ids=["one-resumed", "three-lanes", "all-fresh", "wider-chunk-than-round",
        "one-row-segments", "rows-not-whole-chunks"])
def test_the_chunked_the_sequential_and_the_one_token_form_agree(
        t, segs, before, chunk):
    """On the outputs AND on the state they leave: the chunked form (whole
    chunks as matrix products, the state carried across the chunks of a
    segment) against the scan a row, and against one call of the one-token
    form a row; slots of lanes without a segment and the other layer bit for
    bit as they were."""
    rng = np.random.default_rng(len(segs) + t)
    v, (row_lane, flags) = _segments(rng, t, segs, before)
    y0, s0 = ssd.ssd_rows(**v, layer=1, row_lane=row_lane, flags=flags)
    y1, s1 = ssd.chunk_ssd(**v, layer=jnp.int32(1), row_lane=row_lane,
                           flags=flags, chunk=chunk)
    live = np.asarray(row_lane) >= 0
    # float32 sums in another order: a chunk's rows add up in one product
    np.testing.assert_allclose(np.asarray(y1)[live], np.asarray(y0)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s1, s0, rtol=2e-5, atol=2e-6)
    idle = [lane for lane in range(5) if lane not in dict(segs)]
    np.testing.assert_array_equal(s1[0], v["states"][0])
    np.testing.assert_array_equal(s1[1, idle], v["states"][1, idle])
    # ... and token by token through the one-token form on the store
    states, lanes = v["states"], v["states"].shape[1]
    for r in np.nonzero(live)[0]:
        lane = int(row_lane[r])
        put = lambda a: jnp.zeros((lanes,) + a.shape[1:],    # noqa: E731
                                  a.dtype).at[lane].set(a[r])
        y, states = ssd.one_token_ssd(
            put(v["x"]), put(v["dt"]), v["a"], put(v["b"]), put(v["c"]),
            v["d"], states, 1, jnp.arange(lanes) == lane,
            (jnp.arange(lanes) == lane) & ((int(flags[r]) & 4) != 0))
        np.testing.assert_allclose(y[lane], y0[r], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(states, s0, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("live", [[1, 1, 1, 1, 1], [0, 1, 0, 1, 1],
                                  [0, 0, 0, 0, 1], [0, 0, 0, 0, 0]],
                         ids=["all", "some", "last", "none"])
def test_the_one_token_kernel_is_the_xla_form(live):
    """``ssd_step`` in the interpreter against its XLA definition: a live
    lane's output and slot agree (float32 sums in another order), a lane
    without a row keeps its slot bit for bit and reads a number, the other
    layer is not touched, a fresh lane starts from zeros."""
    rng = np.random.default_rng(7)
    v, _ = _segments(rng, 5, [(0, 5)], {}, n=128)
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([0, 1, 0, 0, 1], bool) & live
    args = (v["x"], v["dt"], v["a"], v["b"], v["c"], v["d"], v["states"], 1,
            live, fresh)
    y0, s0 = ssd.one_token_ssd(*args)
    y1, s1 = ssd.one_token_ssd(*args, use_kernel=True)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y1)[on], np.asarray(y0)[on],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s1, s0, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(s1)[1, ~on],
                                  np.asarray(v["states"])[1, ~on])
    np.testing.assert_array_equal(s1[0], v["states"][0])
    # (never what an unvisited block held: ``D x`` of the lane's row alone)
    np.testing.assert_array_equal(
        np.asarray(y1)[~on], np.asarray(v["d"][:, None] * v["x"])[~on])


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


@pytest.mark.parametrize("lanes", [32, 1], ids=["32-lanes", "1-lane"])
def test_mosaic_compiles_the_one_token_kernel_at_the_published_widths(
        one_chip, lanes):
    """Nemotron-3-Nano's widths (64 heads of 64 on 8 groups, state 128), the
    cell's 23 layers of state: what the interpreter cannot refuse, Mosaic
    can (tiling, VMEM, a column of ``dt x`` against a row of ``B``)."""
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = ssd._step_call.lower(
        shape(lanes, 64, 64), shape(lanes, 64), shape(64),
        shape(lanes, 8, 128), shape(lanes, 8, 128),
        shape(23, lanes, 64, 64, 128), shape(1, dtype=jnp.int32),
        shape(lanes, dtype=jnp.bool_), shape(lanes, dtype=jnp.bool_),
        interpret=False).compile()
    assert "ssd_step" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert ssd.step_geometry_error(64, 128) is None
    assert "tiles" in ssd.step_geometry_error(64, 16)


# ------------------------------------------------------ the expert layer ----

def _expert_layer(rng, d=64, e=16, f=24, fs=48):
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    return {"ln2": {"scale": 1 + w(d)},
            "moe": {"router": w(d, e), "bias": w(e) * 0.1, "w1": w(e, d, f),
                    "w2": w(e, f, d)},
            "shared": {"w1": w(d, fs), "w2": w(fs, d)}}


def _served_routed(p, h, spec, first=0, held=None):
    moe = p["moe"] if held is None else dict(
        p["moe"], w1=p["moe"]["w1"][first:first + held],
        w2=p["moe"]["w2"][first:first + held])
    return routed_ffn(moe, h, spec.top_k, jnp.float32, router=spec.router,
                      act="relu2", scale=spec.routed_scale,
                      norm=spec.norm_topk, first=first, held=held)[0]


def test_the_padded_expert_layout_gives_the_unpadded_layers_bits(model):
    """Zero columns of ``w1`` and zero rows of ``w2``: ``relu(0)^2 = 0``
    meets a zero row, so in float32 the padded layer's numbers are the
    published layer's bit for bit.  The experts and the rows are small
    INTEGERS here: every product and sum is then exact in float32 whatever
    order a backend adds in (a product 128 deep is blocked otherwise than
    one 24 deep), so the comparison holds the layout to the bit and not a
    summation order."""
    spec, _ = model
    rng = np.random.default_rng(2)
    p = _expert_layer(rng)
    ints = lambda lim, *s: jnp.asarray(                      # noqa: E731
        rng.integers(-lim, lim + 1, s), jnp.float32)
    p["moe"].update(w1=ints(2, 16, 64, 24), w2=ints(2, 16, 24, 64))
    h = ints(3, 21, 64)
    w1, w2 = nemotron_h_layout(p["moe"]["w1"], p["moe"]["w2"], spec)
    assert w1.shape == (16, 64, 128) and w2.shape == (16, 128, 64)
    assert not np.asarray(w1[:, :, 24:]).any()
    assert not np.asarray(w2[:, 24:]).any()
    np.testing.assert_array_equal(w1[:, :, :24], p["moe"]["w1"])
    np.testing.assert_array_equal(w2[:, :24], p["moe"]["w2"])
    plain = _served_routed(p, h, spec)
    padded = _served_routed(dict(p, moe=dict(p["moe"], w1=w1, w2=w2)), h,
                            spec)
    assert np.abs(np.asarray(plain)).max() > 100
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(plain))
    # numpy in, numpy out (a loader's arrays)
    n1, _n2 = nemotron_h_layout(np.asarray(p["moe"]["w1"]),
                                np.asarray(p["moe"]["w2"]), spec)
    assert isinstance(n1, np.ndarray) and n1.shape == (16, 64, 128)


def test_the_eight_shares_add_up_to_the_uncut_references_layer(model,
                                                                reference):
    """THE SHARE TEST: what each of eight chips computes of an expert layer
    (its two of the sixteen experts, on the rows routed to them over all
    sixteen columns), with the shared expert, which every chip computes
    alike, counted once, adds up to the uncut reference's layer; and each
    served share is the reference's own share."""
    spec, _ = model
    rng = np.random.default_rng(5)
    p = _expert_layer(rng)
    x = jnp.asarray(rng.normal(size=(19, 64)), jnp.float32)
    kw = dict(eps=spec.rms_eps, top_k=spec.top_k, scale=spec.routed_scale,
              norm=spec.norm_topk)
    whole = np.asarray(reference.moe(x, p, first=0, **kw))
    h = np.asarray(reference._rmsnorm(x, p["ln2"]["scale"], spec.rms_eps))
    shared = np.asarray(jnp.square(jax.nn.relu(h @ p["shared"]["w1"]))
                        @ p["shared"]["w2"])
    parts = []
    for first in range(0, 16, 2):
        part = np.asarray(_served_routed(p, jnp.asarray(h), spec, first, 2))
        held = dict(p, moe=dict(p["moe"], w1=p["moe"]["w1"][first:first + 2],
                                w2=p["moe"]["w2"][first:first + 2]))
        want = np.asarray(reference.moe(x, held, first=first, shared=False,
                                        **kw))
        # float32 sums in another order (a grouped product, a loop)
        np.testing.assert_allclose(part, want, rtol=2e-4, atol=2e-5)
        parts.append(part)
    assert sum(np.abs(part).max() > 1e-3 for part in parts) == 8
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=2e-4,
                               atol=5e-5)


# ------------------------------------------------ through the scheduler ----

def test_tiny_hybrid_end_to_end_against_the_reference(model, reference):
    """A prompt of 37 in rounds of 16, 16 and 5 (two round boundaries, and a
    chunk boundary of 8 inside each full round), then decode blocks through
    the state and the pages: every emitted token's log-probability is the
    reference's (float32 both sides: 2e-4 is sums in another order through
    eight layers), what the lane's slots hold is the reference's state, and
    the expert layers counted the reference's assignments."""
    spec, params = model
    cb = _engine(spec, params)
    try:
        assert cb.pool.n_layers == 2
        assert cb.pool.bytes_per_token == 2 * 2 * 2 * 16 * 4
        prompt = np.random.default_rng(1).integers(0, VOCAB, 37).tolist()
        toks, lps = cb.submit(prompt, steps=10, logprobs=True).result(
            timeout=300)
        hyper = reference.hyper_of(CONFIG)
        got = reference.compare(params, prompt, toks, lps, **hyper)
        assert got["logprob_err_max"] < 2e-4 and got["argmax_gap"] == 0
        debug = cb.debug_state()
        state = debug["state"]
        assert state["kind"] == "mamba2" and state["lanes"] == 2
        assert state["zero_starts"] == 1
        assert state["rule"] == {"decode": "xla", "round": "xla"}
        # 3 layers x (4 x 8 x 16 float32 + 3 x 96 float32 tail rows)
        assert state["bytes_per_lane"] == 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
        assert state["slots"]["round"]["touched"] == 3 * 3
        # rounds of 16, 16, 8 rows in chunks of 8: 5 chunks, 5 passes, 37
        # rows, times the three state layers; 9 decode rows a layer
        assert debug["ssd"] == {
            "chunk": 8,
            "decode": {"chunks": 0, "passes": 0, "rows": 0,
                       "one_token_rows": 9 * 3},
            "round": {"chunks": 5 * 3, "passes": 5 * 3, "rows": 37 * 3,
                      "one_token_rows": 0}}
        _logits, want = reference.last_logits(
            params, prompt + toks[:-1], 1, stores=True, **hyper)
        held = debug["last_release"]
        np.testing.assert_allclose(
            np.asarray(cb.state.arrays[0][:, held["lane"]]), want["state"],
            rtol=1e-4, atol=1e-6)
        moe = debug["moe"]
        np.testing.assert_array_equal(moe["assignments"], want["routes"])
        assert sum(map(sum, moe["assignments"])) == 3 * 3 * (37 + 9)
        # both expert products at the SERVED width (off TPU: ragged_dot)
        assert {(p["k"], p["n"]) for p in moe["product"]} == {(64, 128),
                                                              (128, 64)}
    finally:
        cb.shutdown()


def test_a_reused_lane_and_a_preempted_request_are_the_references(
        model, reference):
    """ONE lane: a request after another starts from zeros on the device
    whatever its predecessor left in the slot; a high-priority arrival
    evicts the lane's request mid-decode and the victim prefills again from
    position 0 (prompt + what it emitted) into a slot the other used
    meanwhile.  Every stream's log-probabilities are the reference's.  On
    the kernels' plan, in the interpreter: the one-token rule is ``ssd_step``
    and the K/V walk the ragged kernels."""
    spec, params = model
    rng = np.random.default_rng(9)
    hyper = reference.hyper_of(CONFIG)
    cb = _engine(spec, params, lanes=1, use_kernel=True)
    try:
        assert cb.debug_state()["state"]["rule"] == {"decode": "kernel",
                                                     "round": "xla"}
        # (every stream takes in 19 tokens: one shape of the reference)
        streams = [(rng.integers(0, VOCAB, n).tolist(), steps)
                   for n, steps in ((11, 9), (5, 15))]
        got = [cb.submit(p, steps, logprobs=True).result(timeout=300)
               for p, steps in streams]
        assert cb.debug_state()["state"]["zero_starts"] == 2
        p_low, p_hi = (rng.integers(0, VOCAB, n).tolist() for n in (10, 6))
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 10, on_token=started, logprobs=True)
        assert started.wait(timeout=120)
        f_hi = cb.submit(p_hi, 14, priority=10, logprobs=True)
        started.release()
        got += [f_hi.result(timeout=300), f_low.result(timeout=300)]
        streams += [(p_hi, 14), (p_low, 10)]
        assert cb.preemptions >= 1
        assert cb.debug_state()["state"]["zero_starts"] >= 5
    finally:
        cb.shutdown()
    for (prompt, steps), (toks, lps) in zip(streams, got):
        assert len(toks) == steps
        err = reference.compare(params, prompt, toks, lps, **hyper)
        assert err["logprob_err_max"] < 2e-4 and err["argmax_gap"] == 0


# ------------------------------------------- ONE in-projection a layer ----

def _three_slices_of_one_product(spec, p, h, compute_dtype):
    """THE DEFINITION of :func:`paged_steps._mamba2_in_proj`, as the mixer
    had it before PR 61: one product with the served leaf, cut in the
    published order ``[z | xBC | dt]``."""
    from tpulab.models.transformer import qmat
    din, cd = spec.m2_heads * spec.m2_head_dim, spec.m2_conv_dim
    zxd = (h @ qmat(p["in_proj"], compute_dtype)).reshape(
        h.shape[0] * h.shape[1], -1)
    return zxd[:, :din], zxd[:, din:din + cd], zxd[:, din + cd:]


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel-interpret"])
@pytest.mark.parametrize("program", ["round", "block"])
def test_the_in_projection_is_the_one_products_three_slices(
        model, program, use_kernel, monkeypatch):
    """The tiny hybrid's mixed round (a chunk that crosses a chunk boundary
    of the recurrence and starts mid-sequence, a fresh chunk, two decode
    rows and an idle lane) and its K = 2 decode block on stores filled with
    random numbers, through the tree's in-projection and through its
    definition above: the logits, the picks and EVERY leaf of both stores
    (pages, states, convolution tails) bit for bit.  What
    ``_mamba2_in_proj`` does to keep XLA from computing the product once a
    reader changes no number: the same product of the same operands, the
    same columns to the same readers."""
    from functools import partial

    from helpers_steps import decode_block, mixed_step
    from tpulab.engine import paged_steps
    from tpulab.engine.kv_pool import LaneStateStore, PagedKVPool
    spec, params = model
    lanes, mp = 5, 64 // PAGE
    rng = np.random.default_rng(17)

    def junk(a):
        return jnp.asarray(rng.normal(0, 0.5, a.shape), a.dtype)
    pages = junk(PagedKVPool(
        n_pages=1 + lanes * mp, page_size=PAGE,
        n_layers=len(spec.attention_layers), n_heads=spec.n_kv_heads,
        head_dim=spec.head_dim, dtype=jnp.float32).kv)
    state = tuple(junk(a) for a in LaneStateStore(spec, lanes,
                                                  jnp.float32).arrays)
    tables = 1 + np.arange(lanes * mp).reshape(lanes, mp)
    kw = dict(lanes=lanes, max_pages=mp, n_heads=spec.n_heads,
              n_layers=spec.n_layers, spec=spec, compute_dtype=jnp.float32,
              use_kernel=use_kernel)
    ctx = np.asarray([9, 0, 21, 7, 0])

    def run(form):
        with monkeypatch.context() as patch:
            if form == "definition":
                patch.setattr(paged_steps, "_mamba2_in_proj",
                              _three_slices_of_one_product)
            if program == "round":
                fn = jax.jit(partial(paged_steps.paged_mixed_step, **kw))
                toks, row_lane, row_off, q_lens = paged_steps.pack_round(
                    lanes, {2: np.arange(11) % VOCAB, 1: np.arange(4) + 5},
                    {0: 3, 3: 8})
                kv_lens = np.where(q_lens > 0, ctx + q_lens, 0)
                picks, lps, last, kv, _moe = mixed_step(
                    fn, params, (pages, state), tables, toks, row_lane,
                    row_off, q_lens, kv_lens, spec=spec)
                out = [picks, lps, np.asarray(last)[q_lens > 0]]
            else:
                fn = jax.jit(partial(paged_steps.paged_decode_block, k=2,
                                     **kw))
                live = np.asarray([1, 0, 1, 1, 0], bool)
                picks, lps, emitted, _carry, kv, _moe = decode_block(
                    fn, params, (pages, state), tables,
                    (ctx + 1, np.asarray([3, 0, 8, 1, 0]), live,
                     np.full(lanes, 2)), 2, spec=spec)
                assert emitted[live].all()
                out = [picks[live], lps[live]]
        return out + [np.asarray(a) for a in jax.tree.leaves(kv)]

    got, want = run("tree"), run("definition")
    assert len(got) == len(want) >= 5
    assert np.isfinite(got[1]).all()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_each_in_proj_leaf_is_read_by_one_product_and_never_copied(
        one_chip, monkeypatch):
    """The round and the K = 2 block at Nemotron-3-Nano's widths, pattern
    ``MM*`` (8 lanes, 128 prompt rows: what compiles here in well under a
    minute), compiled for the described v5e with the kernels Mosaic's: each
    program holds ONE product of the in-projection's width a Mamba-2 layer,
    and no ``copy`` and no fusion that writes a ``bf16[2688, ...]`` array: the
    55 MB weight is read where it lies, whole, once (a form that cut the
    leaf by columns could cost a copy of it a layer in every decode step).

    This guards the WEIGHT COPY, not the clones: a shallow program has
    none.  XLA computed the product once a reader in the 52-layer round
    alone (``tools/xla_clones.py`` on the chip's by-operation table is the
    judge of that: PERF.md section 6, PR 61)."""
    from test_tools import _xla_clones
    from tpulab.tpu import platform
    tool = _xla_clones()
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    with open(os.path.join(ROOT, "perf", "configs",
                           "nemotron3-nano-ep8.json"), encoding="utf-8") as f:
        published = json.load(f)
    spec = nemotron_h_spec(dict(published, num_hidden_layers=3,
                                hybrid_override_pattern="MM*"))
    assert (spec.d_model, spec.m2_conv_dim) == (2688, 6144)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          init_params(spec, 512, 0))
    assert params["layer1"]["mamba2"]["in_proj"].shape == (2688, 10304)
    cb = ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                           lanes=8, max_len=2048, page_size=16, n_pages=256,
                           compute_dtype=jnp.bfloat16, use_kernel=True)
    try:
        fields = cb.programs.fields
        size = lambda kind: sum(
            int(np.prod([n for n in shape if n >= 0])) for _n, _d, shape
            in fields[kind])
        there = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
        buffers = {
            "round": (cb.programs.mixed, jnp.zeros(
                (size("round") - 3 + 3 * (128 + cb.lanes),), jnp.int32)),
            "block": (cb.programs.block(2), jnp.zeros((size("block"),),
                                                      jnp.int32))}
        for name, (program, buffer) in buffers.items():
            text = program.lower(there(cb.params), there(cb._kv_state),
                                 there(buffer),
                                 there(cb._no_carry)).compile().as_text()
            assert "tpu_custom_call" in text and "ssd_step" in text
            compiled = tool.Program.from_text(name, text.splitlines())
            products = [i for i, _ in compiled.insts
                        if i.shape.endswith(",10304]")
                        and compiled.holds_product(i)]
            assert len(products) == 2, (name, products)
            assert not compiled.clones(), name
            moved = [i for i, _ in compiled.insts
                     if i.shape in ("bf16[2688,10304]", "bf16[2688,4096]",
                                    "bf16[2688,6144]", "bf16[2688,64]")
                     and i.opcode in ("copy", "fusion", "slice",
                                      "dynamic-slice", "transpose")]
            assert not moved, (name, moved)
    finally:
        cb.shutdown()


# ------------------------------------------------- the lowered programs ----

def _hashes(texts):
    return [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]


def test_a_mixer_only_layer_lowers_no_ffn_and_the_programs_are_pinned():
    """A pattern of ``M*`` pairs alone lowers with no expert scope, no FFN
    product and no second norm anywhere: a layer of kind ``"none"`` spends
    nothing on the absent half.  The tiny hybrid's tick, round and K = 2
    block are held to ``step_programs_pr61.json``: PR 61 put the Mamba-2
    in-projection's three column blocks behind a barrier, which every
    program of this kind runs, so all three differ from PR 60's file; the
    ten older kinds are PR 60's file letter for letter, which repeats PR
    59's (``tests/test_step_programs.py`` holds the programs to that)."""
    from test_step_programs import _lowered
    small = dict(lanes=2, max_len=64, page_size=8)
    lone = nemotron_h_spec(dict(CONFIG, hybrid_override_pattern="M*M*",
                                num_hidden_layers=4))
    assert lone.moe_layers == () and lone.layer_kinds == ("none",) * 4
    params = init_params(lone, VOCAB, 0)
    assert all("ln2" not in params[f"layer{i}"] and "moe" not in params[
        f"layer{i}"] for i in range(4))
    _texts, scoped = _lowered(lone, VOCAB, 0, small)
    assert "mamba2_ssd" in scoped and "mamba2_norm" in scoped
    assert "moe_" not in scoped
    texts, scoped = _lowered(nemotron_h_spec(CONFIG), VOCAB, 0, small)
    for scope in ("mamba2_proj", "mamba2_conv", "mamba2_ssd", "mamba2_norm",
                  "mamba2_out", "moe_router", "moe_experts", "moe_shared"):
        assert scope in scoped, scope
    with open(GOLDEN, encoding="utf-8") as f:
        golden = json.load(f)["programs"]
    with open(GOLDEN_PR60, encoding="utf-8") as f:
        pr60 = json.load(f)["programs"]
    with open(GOLDEN_PR59, encoding="utf-8") as f:
        pr59 = json.load(f)["programs"]
    assert _hashes(texts) == golden["nemotron"], _hashes(texts)
    assert all(a != b for a, b in zip(golden["nemotron"], pr60["nemotron"]))
    assert sum(t.count("optimization_barrier") for t in texts) == 3 * 3
    older = {k: v for k, v in golden.items() if k != "nemotron"}
    assert older == {k: v for k, v in pr60.items() if k != "nemotron"} == pr59


if __name__ == "__main__":
    # the golden, written again from this tree
    from tpulab.tpu.platform import force_cpu
    force_cpu(8)
    from test_step_programs import _lowered
    with open(GOLDEN_PR60, encoding="utf-8") as f:
        golden = {"programs": json.load(f)["programs"]}
    golden["programs"]["nemotron"] = _hashes(_lowered(
        nemotron_h_spec(CONFIG), VOCAB, 0,
        dict(lanes=2, max_len=64, page_size=8))[0])
    print(json.dumps(golden, indent=1))
