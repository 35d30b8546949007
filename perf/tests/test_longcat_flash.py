"""Kind ``longcat_flash``: the reference against cases written out by hand, the
rooflines' counts against the issue's reckoning, the new readers on canned
contexts, the new cell's files, and a tiny overlay cell (a share of the
experts) through ``perf/run.py`` end to end on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "longcat_flash")
ROOFLINE = spec.load_module("rooflines", "longcat_flash")
LONGCAT = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                      "longcat-flash-l4-ep32.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-longcat.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "longcat-flash-l4-ep32.agent"


# ------------------------------------------------------- the reference ----

def test_rope_turns_interleaved_pairs():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((5, 2, 8)), jnp.float32)
    got = np.asarray(REF._rope(x, jnp.arange(5), 1e4))
    np.testing.assert_array_equal(got[0], np.asarray(x)[0])   # position 0
    ang = 3 * 1e4 ** -(np.arange(4) / 4)
    x3 = np.asarray(x)[3, 1]
    want = np.empty(8)
    want[0::2] = x3[0::2] * np.cos(ang) - x3[1::2] * np.sin(ang)
    want[1::2] = x3[1::2] * np.cos(ang) + x3[0::2] * np.sin(ang)
    np.testing.assert_allclose(got[3, 1], want, rtol=1e-5, atol=1e-6)


def _tiny_block(rng, d=8, e=6, z=3, f=4):
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.5, jnp.float32)
    return {"router": w(d, e + z), "bias": w(e + z) / (e + z),
            "w13": w(e, d, 2 * f), "w2": w(e, f, d)}


def test_moe_block_by_hand_and_the_shares_add_up():
    """Top-3 of 6 + 3 identity columns by hand in float64: softmax over all
    nine, the choice by ``s + b``, the weight ``6 s`` not renormalised, an
    identity column adds ``weight x h``; three shares of two experts, with
    the identity part counted once, are the uncut block."""
    rng = np.random.default_rng(5)
    m = _tiny_block(rng)
    h = rng.standard_normal((7, 8)).astype(np.float32)
    f64 = lambda a: np.asarray(a, np.float64)
    silu = lambda v: v / (1 + np.exp(-v))
    logits = f64(h) @ f64(m["router"])
    s = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    routed, identity = np.zeros((7, 8)), np.zeros((7, 8))
    for t in range(7):
        for e in np.argsort(-(s[t] + f64(m["bias"])), kind="stable")[:3]:
            if e >= 6:
                identity[t] += 6 * s[t, e] * f64(h[t])
                continue
            w13, w2 = f64(m["w13"][e]), f64(m["w2"][e])
            routed[t] += 6 * s[t, e] * (
                (silu(f64(h[t]) @ w13[:, :4]) * (f64(h[t]) @ w13[:, 4:])) @ w2)
    kw = dict(top_k=3, scale=6.0, n_zero=3)
    got = np.asarray(REF.moe(jnp.asarray(h), m, **kw))
    np.testing.assert_allclose(got, routed + identity, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(REF.moe(jnp.asarray(h), m, routed=False, **kw)), identity,
        rtol=2e-5, atol=2e-6)
    assert np.abs(identity).max() > 1e-3 and np.abs(routed).max() > 1e-3
    parts = []
    for first in (0, 2, 4):
        held = dict(m, w13=m["w13"][first:first + 2],
                    w2=m["w2"][first:first + 2])
        parts.append(np.asarray(REF.moe(jnp.asarray(h), held, first=first,
                                        identity=False, **kw)))
    np.testing.assert_allclose(sum(parts) + identity, routed + identity,
                               rtol=2e-5, atol=2e-6)
    assert all(np.abs(part).max() > 1e-3 for part in parts)


def test_hyper_of_reads_the_published_keys_and_the_share():
    hyper = REF.hyper_of(LONGCAT)
    assert (hyper["n_layers"], hyper["n_heads"], hyper["nope"],
            hyper["v_dim"]) == (4, 64, 128, 128)
    assert hyper["q_scale"] == 2.0 and hyper["kv_scale"] == 12 ** 0.5
    assert (hyper["top_k"], hyper["routed_scaling_factor"], hyper["n_zero"],
            hyper["first"]) == (12, 6.0, 256, 0)
    assert (hyper["rms_norm_eps"], hyper["rope_theta"]) == (1e-5, 1e7)
    assert REF.REFERENCE_STEPS == 32 and REF.REFERENCE_STREAMS == 4
    assert 0 < REF.TOLERANCE < 1
    flat = REF.hyper_of(dict(LONGCAT, mla_scale_q_lora=False,
                             mla_scale_kv_lora=False))
    assert (flat["q_scale"], flat["kv_scale"]) == (1.0, 1.0)


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.PERF_DIR, "reference",
                           "longcat_flash.py")) as f:
        assert "tpulab" not in f.read().split('"""', 2)[2]


def test_summary_judges_the_lower_quartile_of_all_the_streams_tokens():
    streams = [{"err": np.asarray([0.0, 0.1, 0.2, 0.3]),
                "gap": np.zeros(4)},
               {"err": np.asarray([0.4, 0.5, 0.6, 0.7]),
                "gap": np.asarray([0.0, 0.0, 1.0, 1.0])}]
    got = REF.summary(streams)
    assert got["logprob_err"] == pytest.approx(0.175)
    assert got["argmax_gap"] == 0 and got["logprob_err_max"] == 0.7
    assert got["flipped_share"] == 7 / 8


# -------------------------------------------------------- the rooflines ----

def test_parameter_and_cache_counts_are_the_issues():
    """ISSUE 46's own count: an attention 90.57 M, a dense FFN 226.49 M, a
    router 4.72 M, a layer outside its experts 638.9 M, an expert 37.75 M,
    5,172.6 M parameters = 10.35 GB, 9,216 B of latent rows a token."""
    assert ROOFLINE.attention_params(LONGCAT) == (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 8192 * 6144) == 90_570_752
    assert ROOFLINE.dense_ffn_params(LONGCAT) == 3 * 6144 * 12288
    assert ROOFLINE.router_columns(LONGCAT) == 768
    assert ROOFLINE.layer_outside_expert_params(LONGCAT) == (
        2 * 90_570_752 + 2 * 226_492_416 + 6144 * 768) == 638_844_928
    assert ROOFLINE.expert_params(LONGCAT) == 37_748_736
    assert ROOFLINE.model_params(LONGCAT) == (
        4 * (638_844_928 + 16 * 37_748_736) + 2 * 16384 * 6144
    ) == 5_172_625_408
    assert 10.34e9 < 2 * ROOFLINE.model_params(LONGCAT) < 10.35e9
    assert ROOFLINE.latent_layers(LONGCAT) == 8
    assert ROOFLINE.kv_bytes_per_token(LONGCAT) == 9216


def test_step_bytes_and_round_flops_are_the_issues_table():
    """A decode step of 32 lanes at ~5 k keys with ~6 of 16 experts hit a
    layer: 5.1 GB outside the experts, 1.8 GB of experts, 0.2 GB of head,
    1.5 GB of latent rows; a round of 544 rows: 2.8 TFLOP of projections
    and dense FFNs before the attention's pairs."""
    got = ROOFLINE.decode_step_bytes(LONGCAT, 32, 6, 5000)
    assert got == (2 * (4 * 638_844_928 + 4 * 6 * 37_748_736 + 16384 * 6144)
                   + 32 * 5000 * 9216)
    assert 8.5e9 < got < 8.8e9
    assert ROOFLINE.decode_step_bytes(LONGCAT, 0, 0, 0) == 2 * (
        4 * 638_844_928 + 16384 * 6144)
    whole = ROOFLINE.round_bytes(LONGCAT, 20, 4000)
    assert whole == 2 * (5_172_625_408 - 16384 * 6144) + 20 * 4000 * 9216
    assert ROOFLINE.attention_pair_flops(LONGCAT) == 2 * 64 * (576 + 512)
    rows = ROOFLINE.round_flops(LONGCAT, 544, 0, 0, 0)
    assert rows == 2 * 544 * 4 * 638_844_928 and 2.7e12 < rows < 2.9e12
    assert ROOFLINE.round_flops(LONGCAT, 0, 10, 0, 0) == 20 * 37_748_736
    assert ROOFLINE.round_flops(LONGCAT, 0, 0, 1000, 0) == (
        1000 * 8 * 2 * 64 * 1088)
    assert ROOFLINE.round_flops(LONGCAT, 0, 0, 0, 3) == 6 * 16384 * 6144


# -------------------------------------------------------- the readers ----

class _Cell:
    config = LONGCAT

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None):
    def moe(scale):
        # columns weigh 1, 2, 3 in turn: the identity columns 512.. as the rest
        rows = [[scale * (1 + (e % 3)) for e in range(768)] for _ in range(4)]
        return {"expert_layers": [0, 2, 4, 6], "assignments": rows,
                "zero_first": 512, "zero_columns": 256, "first": 0,
                "held": 16, "assignments_here": [sum(r[:16]) for r in rows],
                "decode_steps": 10 * scale, "experts_hit": 4 * 70 * scale}
    pool = {"n_pages": 16385, "page_size": 16,
            "hbm_bytes": 16385 * 16 * 10240}

    def dispatch(scale):
        # 30 lanes a decode step at 5,000 keys a lane; a round of 512 prompt
        # tokens and 20 decode rows: 21 lanes at 4,000 keys a lane
        return {"decode_block_steps": 100 * scale, "mixed_tokens":
                40 * 532 * scale, "mixed_rows": 40 * 544 * scale, "kinds": {
                    "decode": 50 * scale, "mixed": 40 * scale, "verify": 0},
                "round_attn_pairs": 40 * 1_400_000 * scale,
                "lane_work": {
                    "decode": {"passes": 3000 * scale, "rows": 3000 * scale,
                               "keys": 3000 * 5000 * scale},
                    "round": {"passes": 840 * scale, "rows": 40 * 532 * scale,
                              "keys": 840 * 4000 * scale}}}
    return {"cell": _Cell, "trace": trace, "gauges": [], "say": None,
            "counters_before": {"moe": moe(1), "pool": pool,
                                "dispatch": dispatch(1)},
            "counters_after": {"moe": moe(3), "pool": pool,
                               "dispatch": dispatch(3)}}


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx()
    # columns 512 .. 767 of the pattern 1, 2, 3: 85 triples and a 3, of 1536
    assert read("moe.zero_expert_share", ctx) == pytest.approx(
        100 * 513 / 1536)
    assert read("kv.bytes_per_token", ctx) == 10240
    assert read("moe.experts_hit_per_step", ctx) == 7
    # columns 0..15: 6 + 2 * 5 + 3 * 5 of 1536, an even router 16 of 768
    assert read("moe.assignments_here_skew", ctx) == pytest.approx(
        100 * abs(31 / 1536 - 16 / 768))
    assert read("moe.expert_load_max_over_mean", ctx) == pytest.approx(1.5)
    mfu = spec.load_module("layer_metrics", "scmoe.round_mfu")
    tokens, expert_rows, pairs, lanes = mfu.round_work(ctx)
    assert (tokens, pairs, lanes) == (532, 1_400_000, 21)
    # 4 layers x 31 here a pattern, the rounds' share of the rows routed
    assert expert_rows == pytest.approx(
        2 * 4 * 31 * (2 * 40 * 532) / (2 * 40 * 532 + 2 * 3000) / 80)
    for name in ("scmoe.decode_roofline", "scmoe.round_mfu"):
        assert read(name, ctx) is None                          # no trace
    # a program without the counters (the parent) or a model without
    # identity columns: nothing to read, no error
    glm = {"dispatch": {}, "moe": {"expert_layers": [0],
                                   "assignments": [[1, 2]],
                                   "decode_steps": 3, "experts_hit": 4}}
    for old in ({"dispatch": {}}, glm):
        bare = {"cell": _Cell, "trace": {"modules": {"jit_paged_mixed_step": {
            "durations_s": [0.01]}}}, "gauges": [], "counters_before": old,
            "counters_after": old}
        for name in ("scmoe.decode_roofline", "scmoe.round_mfu",
                     "moe.zero_expert_share"):
            assert read(name, bare) is None
    # a program with the columns but without ``round_attn_pairs`` / ``lane_work``
    old = dict(ctx["counters_after"], dispatch={
        "decode_block_steps": 3, "kinds": {"mixed": 2}, "mixed_tokens": 9})
    bare = dict(ctx, trace={"modules": {"jit_paged_mixed_step": {
        "durations_s": [0.01]}, "jit_paged_decode_block_k2": {
        "durations_s": [0.01]}}}, counters_before=old, counters_after=old)
    for name in ("scmoe.decode_roofline", "scmoe.round_mfu"):
        assert read(name, bare) is None


def test_shares_are_bytes_and_flops_over_the_peaks_over_mean_time(
        monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.026, 0.028]},
        "jit_paged_decode_block_k1": {"durations_s": [0.014]},
        "jit_paged_mixed_step": {"durations_s": [0.033, 0.035]}}}
    ctx = _ctx(trace)
    said = []
    ctx["say"] = said.append
    read = lambda name: spec.load_module("layer_metrics", name).read(ctx)
    step = (0.026 + 0.028 + 0.014) / (2 + 2 + 1)
    assert read("scmoe.decode_roofline") == pytest.approx(
        100 * ROOFLINE.decode_step_bytes(LONGCAT, 30, 7, 5000) / 819e9 / step)
    mfu = spec.load_module("layer_metrics", "scmoe.round_mfu")
    work = mfu.round_work(ctx)
    assert read("scmoe.round_mfu") == pytest.approx(
        100 * ROOFLINE.round_flops(LONGCAT, *work) / 197e12 / 0.034)
    floors = mfu.bounds(ctx)
    assert floors["bytes_s"] == pytest.approx(
        ROOFLINE.round_bytes(LONGCAT, 21, 4000) / 819e9)
    assert floors["flops_s"] > floors["bytes_s"]      # past the ridge
    assert said and "at the HBM bandwidth" in said[0]
    assert 0 < read("scmoe.decode_roofline") < 100
    assert 0 < read("scmoe.round_mfu") < 100


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "longcat-flash-l4-ep32", "agent-closed-c32")
    c = cell.config
    assert c["kind"] == "longcat_flash"
    assert c["reduced"].keys() == {"num_layers", "n_routed_experts",
                                   "vocab_size"}
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) == (
        4, 16, 16384)
    assert c["share"] == dict(c["share"], chips=32, n_routed_experts=512,
                              first_expert=0, vocab_size=131072,
                              first_vocab_row=0)
    assert (c["zero_expert_num"], c["moe_topk"]) == (256, 12)
    assert {"assumed", "departures", "stands_for",
            "kv_bytes_per_token"} <= set(c)
    assert {"hidden_act", "router_bias", "norm_topk_prob", "torch_dtype",
            "rope", "weights", "tie_word_embeddings"} <= set(c["assumed"])
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 16384,
                                      "page_size": 16, "pool_tokens": 262144}
    assert cell.traffic["concurrency"] == cell.traffic["set_size"] == 32
    assert cell.traffic["reference_prompt_lens"] == [24, 4000]
    assert (cell.traffic["pairing_seed"], cell.traffic["channels"],
            cell.traffic["ramp_max_s"]) == (1, 4, 120)
    names = {m["name"] for m in cell.per_layer}
    assert {"moe.zero_expert_share", "scmoe.decode_roofline",
            "scmoe.round_mfu", "moe.assignments_here_skew",
            "moe.experts_hit_per_step", "moe.expert_load_max_over_mean",
            "kv.bytes_per_token"} <= names
    assert not {"gdn.decode_roofline", "ssm.decode_roofline",
                "step.decode_weight_roofline"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "longcat_flash")
    sp = cell.module("models", "longcat_flash").spec_of(c)
    assert (sp.n_layers, sp.n_experts, sp.zero_experts, sp.experts_held,
            sp.expert_first, sp.top_k) == (8, 768, 256, 16, 0, 12)
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Chat")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"])
        assert c["source"] == row["source_url"]
        assert {k: c["share"][k] for k in ("n_routed_experts",
                                           "vocab_size")} == {
            k: row["config"][k] for k in ("n_routed_experts", "vocab_size")}


def test_the_mix_is_the_issues_and_no_operation_can_fail():
    """agent: prompts 1731-9695, outputs 149-439: the pool holds the whole
    set at once (no preemption) and max_len the longest pair."""
    from harness.sizes import size_pairs
    traffic = spec.load_json(os.path.join(spec.PERF_DIR, "traffic",
                                          "agent-closed-c32.json"))
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                     "sigma": 0.4, "min": 1024, "max": 12288}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.25, "min": 96, "max": 768}
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (1731, 9695)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (149, 439)
    eng = traffic["engine"]
    assert pairs.sum() <= eng["pool_tokens"]
    assert pairs.sum(1).max() <= eng["max_len"]
    assert traffic["generator"] == "closed_replay"


def test_adapter_draws_the_bias_at_the_scale_of_the_scores_and_lays_out():
    """The selection bias at 1.5 mean scores; the three attention matrices
    drawn as published and put through the program's layout: ``wq_b``
    doubled, the halves of ``kv_b_proj`` times 12^0.5."""
    adapter = spec.load_module("models", "longcat_flash")
    assert adapter.BIAS_STD == 1.5 / 768
    tiny = spec.load_json(os.path.join(
        spec.PERF_DIR, "tests", "cells", "configs", "tiny-longcat.json"))
    sp = adapter.spec_of(tiny)
    assert (sp.n_experts, sp.experts_held, sp.expert_first) == (12, 2, 2)
    from functools import partial

    from tpulab.models.spec import init_params
    tree = jax.eval_shape(partial(init_params, sp, 256, 96))
    params = adapter.make_weights(tree, sp, tiny, 2**31 + 5)
    bias = np.asarray(params["layer0"]["moe"]["bias"], np.float32)
    assert 0.2 * adapter.BIAS_STD < bias.std() < 3 * adapter.BIAS_STD
    assert (np.asarray(params["layer1"]["ln1"]["scale"], np.float32)
            == 1).all()
    w1 = np.asarray(params["layer1"]["w1"], np.float32)
    assert 0.017 < w1.std() < 0.023
    key = adapter.weights_key(2**31 + 5)
    wq_b, wkv_a, kv_b = (np.asarray(w, np.float32) for w in
                         adapter.published_attention(sp, key, 3))
    got = params["layer3"]
    nope = sp.qk_nope_head_dim
    np.testing.assert_array_equal(
        np.asarray(got["wq_b"], np.float32).reshape(16, 4, 20)[..., :nope],
        2 * wq_b.reshape(16, 4, 20)[..., :nope])
    np.testing.assert_array_equal(np.asarray(got["wkv_a"], np.float32)[
        :, :32], wkv_a[:, :32])
    want = (2 ** 0.5 * kv_b.reshape(32, 4, 28)[:, :, :nope]).transpose(1, 2, 0)
    np.testing.assert_allclose(np.asarray(got["w_uk"], np.float32), want,
                               rtol=2 ** -8)


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_longcat_cell_end_to_end_on_the_cpu():
    """The tiny cell holds FFN experts 2 .. 4 of 8 beside 4 identity
    columns: the served path (its layout folded at load) and the reference
    (the published matrices) leave the same six experts out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-longcat.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    got = line["metrics"]
    assert got["kv.bytes_per_token"]["value"] == 4 * 128 * 2
    assert 10 < got["moe.zero_expert_share"]["value"] < 60
    assert got["compiles_in_window.lm"]["value"] == 0
    assert "rehearsal" in line
