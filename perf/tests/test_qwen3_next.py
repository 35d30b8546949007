"""Kind ``qwen3_next``: the reference against hand-unrolled cases, the
rooflines' counts against the issue's reckoning, the new readers on canned
contexts, the new cells' files, and a tiny overlay cell (a share of the
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

REF = spec.load_module("reference", "qwen3_next")
ROOFLINE = spec.load_module("rooflines", "qwen3_next")
QWEN = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                   "qwen3next-l8-ep4.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-qwen3next.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ------------------------------------------------------- the reference ----

def test_delta_rule_against_two_tokens_unrolled_by_hand():
    """Two tokens, one head, in float64 numpy with every step written out:
    token 0 writes ``beta k (x) v`` into an empty state, token 1 decays it,
    corrects it towards its own value and reads it."""
    rng = np.random.default_rng(3)
    q, k = rng.standard_normal((2, 1, 4)), rng.standard_normal((2, 1, 4))
    v = rng.standard_normal((2, 1, 3))
    g, beta = -rng.uniform(0.1, 1, (2, 1)), rng.uniform(0.1, 0.9, (2, 1))
    s0 = beta[0, 0] * np.outer(k[0, 0], v[0, 0])
    o0 = s0.T @ q[0, 0]
    s1 = np.exp(g[1, 0]) * s0
    s1 = s1 + np.outer(k[1, 0], beta[1, 0] * (v[1, 0] - s1.T @ k[1, 0]))
    f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    got, state = REF.delta_rule(f32(q), f32(k), f32(v), f32(g), f32(beta))
    np.testing.assert_allclose(np.asarray(got)[:, 0], [o0, s1.T @ q[1, 0]],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(state)[0], s1, rtol=2e-5,
                               atol=2e-6)


def test_rope_turns_the_first_columns_and_passes_the_rest():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((5, 2, 16)), jnp.float32)
    pos = jnp.arange(5)
    got = np.asarray(REF._rope(x, pos, 1e4, 4))
    np.testing.assert_array_equal(got[..., 4:], np.asarray(x)[..., 4:])
    np.testing.assert_array_equal(got[0], np.asarray(x)[0])   # position 0
    # rotate-half over 4 columns: pairs (0, 2) and (1, 3)
    ang = 3 * 1e4 ** -(np.arange(2) / 2)
    x3 = np.asarray(x)[3, 1]
    want = np.concatenate([x3[:2] * np.cos(ang) - x3[2:4] * np.sin(ang),
                           x3[2:4] * np.cos(ang) + x3[:2] * np.sin(ang)])
    np.testing.assert_allclose(got[3, 1, :4], want, rtol=1e-5, atol=1e-6)
    whole = np.asarray(REF._rope(x, pos, 1e4, 16))
    assert np.abs(whole[3, 1, 4:] - np.asarray(x)[3, 1, 4:]).max() > 1e-3


def _tiny_layer(rng, d=8, e=6, f=4):
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.5, jnp.float32)
    return {"ln2": {"scale": 1 + w(d)},
            "moe": {"router": w(d, e), "w13": w(e, d, 2 * f), "w2": w(e, f, d)},
            "shared": {"w1": w(d, f), "w3": w(d, f), "w2": w(f, d),
                       "gate": w(d, 1)}}


def test_moe_block_by_hand_and_the_shares_add_up():
    """Top-2 of 6 by hand in float64; three shares of two experts, with the
    gated shared expert counted once, are the uncut block."""
    rng = np.random.default_rng(5)
    p = _tiny_layer(rng)
    x = rng.standard_normal((7, 8)).astype(np.float32)
    f64 = lambda a: np.asarray(a, np.float64)
    silu = lambda v: v / (1 + np.exp(-v))
    h = f64(x) / np.sqrt((f64(x) ** 2).mean(-1, keepdims=True) + 1e-6) \
        * f64(p["ln2"]["scale"])
    logits = h @ f64(p["moe"]["router"])
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = np.zeros((7, 8))
    for t in range(7):
        top = np.argsort(-probs[t], kind="stable")[:2]
        for e in top:
            w13, w2 = f64(p["moe"]["w13"][e]), f64(p["moe"]["w2"][e])
            want[t] += probs[t, e] / probs[t, top].sum() * (
                (silu(h[t] @ w13[:, :4]) * (h[t] @ w13[:, 4:])) @ w2)
    s = p["shared"]
    shared = ((silu(h @ f64(s["w1"])) * (h @ f64(s["w3"]))) @ f64(s["w2"])
              / (1 + np.exp(-(h @ f64(s["gate"])))))
    kw = dict(eps=1e-6, top_k=2)
    got = np.asarray(REF.moe(jnp.asarray(x), p, first=0, **kw))
    np.testing.assert_allclose(got, want + shared, rtol=2e-5, atol=2e-6)
    parts = []
    for first in (0, 2, 4):
        held = dict(p, moe=dict(p["moe"], w13=p["moe"]["w13"][first:first + 2],
                                w2=p["moe"]["w2"][first:first + 2]))
        parts.append(np.asarray(REF.moe(jnp.asarray(x), held, first=first,
                                        shared=False, **kw)))
    np.testing.assert_allclose(sum(parts) + shared, want + shared, rtol=2e-5,
                               atol=2e-6)
    assert all(np.abs(part).max() > 1e-3 for part in parts)


def test_hyper_of_reads_the_published_keys_and_the_share():
    hyper = REF.hyper_of(QWEN)
    assert (hyper["n_layers"], hyper["period"], hyper["rot"]) == (8, 4, 64)
    assert (hyper["k_heads"], hyper["v_heads"], hyper["d_k"],
            hyper["d_v"]) == (16, 32, 128, 128)
    assert (hyper["n_heads"], hyper["n_kv_heads"], hyper["head_dim"]) == (
        16, 2, 256)
    assert hyper["top_k"] == 10 and hyper["first"] == 0
    assert REF.REFERENCE_STEPS == 32 and REF.REFERENCE_STREAMS == 4
    assert 0 < REF.TOLERANCE < 1


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.PERF_DIR, "reference", "qwen3_next.py")) as f:
        assert "tpulab" not in f.read().split('"""', 2)[2]


# -------------------------------------------------------- the rooflines ----

def test_parameter_and_state_counts_are_the_issues():
    """ISSUE 39's own count: a Gated DeltaNet mixer 33.72 M, an attention
    mixer 27.26 M, a layer's router and shared expert 4.20 M, an expert
    3.146 M, 290.4 M outside the experts, 3,667 M parameters = 7.33 GB."""
    assert ROOFLINE.gdn_params(QWEN) == (
        2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048) == 33_718_272
    assert ROOFLINE.attention_params(QWEN) == (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) == 27_262_976
    assert ROOFLINE.ffn_shared_params(QWEN) == (
        2048 * 512 + 3 * 2048 * 512 + 2048) == 4_196_352
    assert ROOFLINE.expert_params(QWEN) == 3_145_728
    assert ROOFLINE.n_attention_layers(QWEN) == 2
    assert ROOFLINE.outside_expert_params(QWEN) == (
        6 * 33_718_272 + 2 * 27_262_976 + 8 * 4_196_352) == 290_406_400
    assert ROOFLINE.model_params(QWEN) == (
        290_406_400 + 8 * 128 * 3_145_728 + 2 * 37_984 * 2048
    ) == 3_667_214_336
    assert 7.33e9 < 2 * ROOFLINE.model_params(QWEN) < 7.34e9
    assert ROOFLINE.state_bytes_per_lane(QWEN) == 6 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2) == 12_877_824
    assert ROOFLINE.kv_bytes_per_token(QWEN) == 4096


def test_step_and_round_bytes_are_the_issues_table():
    """A decode step of 32 lanes at ~2.5 k of context with ~60 of 128
    experts hit a layer: 3.0 GB of experts, 0.83 GB of state, 0.58 GB
    outside the experts, 0.33 GB of K/V rows, 0.16 GB of head: ~4.9 GB."""
    got = ROOFLINE.decode_step_bytes(QWEN, 32, 60, 2500)
    assert got == (2 * (290_406_400 + 8 * 60 * 3_145_728 + 37_984 * 2048)
                   + 32 * (2 * 12_877_824 + 2500 * 4096))
    assert 4.85e9 < got < 4.95e9
    # no lane, no expert: the weights every step reads
    assert ROOFLINE.decode_step_bytes(QWEN, 0, 0, 0) == 2 * (
        290_406_400 + 37_984 * 2048)
    whole = ROOFLINE.round_bytes(QWEN, 32, 2500)
    assert whole == (2 * (3_667_214_336 - 37_984 * 2048)
                     + 32 * (2 * 12_877_824 + 2500 * 4096))
    assert whole > got


def test_chunk_delta_rule_cost_counts_passes_heads_and_states():
    cost = ROOFLINE.chunk_delta_rule_cost(256, 32)
    per_pass = 2 * (3 * 64 * 64 * 128 + 2 * 64 * 64 * 128 + 10 * 64 ** 3
                    + 3 * 64 * 128 * 128)
    assert cost["flops"] == 4 * 32 * per_pass
    assert cost["bytes"] == 4 * (256 * 32 * 512 + 2 * 32 * 128 * 128)
    assert ROOFLINE.chunk_delta_rule_cost(65, 1)["flops"] == 2 * per_pass
    assert (ROOFLINE.chunk_delta_rule_cost(256, 32, segments=3)["bytes"]
            - cost["bytes"]) == 4 * 2 * 2 * 32 * 128 * 128


# -------------------------------------------------------- the readers ----

class _Cell:
    config = QWEN

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None):
    def moe(scale):
        rows = [[scale * (1 + (e % 3)) for e in range(512)] for _ in range(8)]
        return {"expert_layers": list(range(8)), "assignments": rows,
                "first": 0, "held": 128,
                "assignments_here": [sum(r[:128]) for r in rows],
                "decode_steps": 10 * scale, "experts_hit": 8 * 600 * scale}
    state = {"kind": "gdn", "lanes": 32, "bytes_per_lane": 12_877_824,
             "hbm_bytes": 32 * 12_877_824, "zero_starts": 7}
    pool = {"n_pages": 20481, "page_size": 16, "hbm_bytes": 20481 * 16 * 4096}
    def dispatch(scale):
        # 31 lanes a decode step at 2,500 keys a lane; a round of one chunk
        # lane and five decoding lanes at 2,000 keys a lane
        return {"decode_block_steps": 100 * scale, "kinds": {
                    "decode": 50 * scale, "mixed": 40 * scale, "verify": 0},
                "lane_work": {
                    "decode": {"passes": 3100 * scale, "rows": 3100 * scale,
                               "keys": 3100 * 2500 * scale},
                    "round": {"passes": 240 * scale, "rows": 10440 * scale,
                              "keys": 240 * 2000 * scale}}}
    return {"cell": _Cell, "trace": trace, "gauges": [],
            "counters_before": {"moe": moe(1), "state": state, "pool": pool,
                                "dispatch": dispatch(1)},
            "counters_after": {"moe": moe(3), "state": state, "pool": pool,
                               "dispatch": dispatch(3)}}


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx()
    assert read("ssm.state_bytes_per_lane", ctx) == 12_877_824
    assert read("kv.bytes_per_token", ctx) == 4096
    assert read("moe.experts_hit_per_step", ctx) == 60
    # columns 0..127 of the pattern 1, 2, 3: 43 + 2 * 43 + 3 * 42 of 1023,
    # where an even router sends 128 of 512
    assert read("moe.assignments_here_skew", ctx) == pytest.approx(
        100 * abs(255 / 1023 - 0.25))
    at = spec.load_module("layer_metrics",
                          "gdn.decode_roofline").lanes_and_context
    assert at(ctx, "decode", "decode_block_steps") == (31, 2500)
    assert at(ctx, "round", "kinds", "mixed") == (6, 2000)
    for name in ("gdn.decode_roofline", "gdn.round_roofline"):
        assert read(name, ctx) is None                          # no trace
    # a program without the counters (the parent), or a model with another
    # kind of state: nothing to read, no error
    mamba = {"state": {"kind": "mamba", "bytes_per_lane": 1}, "pool": {}}
    for old in ({"dispatch": {}}, mamba, {"dispatch": {}, "moe": {
            "expert_layers": [0], "assignments": [[1, 2]],
            "decode_steps": 3, "experts_hit": 4}}):
        bare = {"cell": _Cell, "trace": {"modules": {}}, "gauges": [],
                "counters_before": old, "counters_after": old}
        for name in ("gdn.decode_roofline", "gdn.round_roofline",
                     "moe.assignments_here_skew"):
            assert read(name, bare) is None
    # a program with the state but without ``lane_work``
    old = dict(ctx["counters_after"], dispatch={"decode_block_steps": 3,
                                                "kinds": {"mixed": 2}})
    bare = {"cell": _Cell, "trace": {"modules": {"jit_paged_mixed_step": {
        "durations_s": [0.01]}}}, "gauges": [], "counters_before": old,
        "counters_after": old}
    for name in ("gdn.decode_roofline", "gdn.round_roofline"):
        assert read(name, bare) is None


def test_rooflines_are_bytes_over_bandwidth_over_mean_time(monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.016, 0.018]},
        "jit_paged_decode_block_k1": {"durations_s": [0.009]},
        "jit_paged_mixed_step": {"durations_s": [0.013, 0.015]}}}
    ctx = _ctx(trace)
    read = lambda name: spec.load_module("layer_metrics", name).read(ctx)
    step = (0.016 + 0.018 + 0.009) / (2 + 2 + 1)
    assert read("gdn.decode_roofline") == pytest.approx(
        100 * ROOFLINE.decode_step_bytes(QWEN, 31, 60, 2500) / 819e9 / step)
    assert read("gdn.round_roofline") == pytest.approx(
        100 * ROOFLINE.round_bytes(QWEN, 6, 2000) / 819e9 / 0.014)
    assert 0 < read("gdn.decode_roofline") < 100
    assert 0 < read("gdn.round_roofline") < 100


# ------------------------------------------------------ the cells' files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell("qwen3next-l8-ep4.rag")
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "qwen3next-l8-ep4", "rag-closed-c32")
    c = cell.config
    assert c["kind"] == "qwen3_next"
    assert c["reduced"].keys() == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        8, 128, 37984)
    assert c["share"]["num_experts"] == 512 and c["share"]["chips"] == 4
    assert c["share"]["first_expert"] == 0 and c["num_experts_per_tok"] == 10
    assert c["share"]["vocab_size"] == 151936 == 4 * c["vocab_size"]
    assert {"assumed", "departures", "stands_for", "state_bytes_per_lane",
            "kv_bytes_per_token"} <= set(c)
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 16384,
                                      "page_size": 16, "pool_tokens": 327680}
    assert cell.traffic["concurrency"] == cell.traffic["set_size"] == 32
    assert cell.traffic["reference_prompt_lens"] == [24, 2000]
    names = {m["name"] for m in cell.per_layer}
    assert {"gdn.decode_roofline", "gdn.round_roofline",
            "moe.assignments_here_skew", "moe.experts_hit_per_step",
            "moe.expert_load_max_over_mean", "kv.bytes_per_token",
            "ssm.state_bytes_per_lane"} <= names
    assert not {"ssm.decode_roofline", "dsa.decode_roofline",
                "step.decode_weight_roofline"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "qwen3_next")
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"])
        assert c["source"] == row["source_url"]
        assert {k: c["share"][k] for k in ("num_experts", "vocab_size")} == {
            k: row["config"][k] for k in ("num_experts", "vocab_size")}


def test_the_mix_is_the_issues_and_no_operation_can_fail():
    """rag: prompts 698-6012, outputs 268-977: a lane's pool share and
    max_len cover the longest pair."""
    from harness.sizes import size_pairs
    traffic = spec.load_json(os.path.join(spec.PERF_DIR, "traffic",
                                          "rag-closed-c32.json"))
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (698, 6012)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (268, 977)
    eng = traffic["engine"]
    assert pairs.sum(1).max() <= eng["pool_tokens"] // eng["lanes"]
    assert pairs.sum(1).max() <= eng["max_len"]
    assert traffic["generator"] == "closed_replay"


def test_store_errors_by_hand_and_what_a_rounded_store_reads():
    """``state_err`` is the difference's norm over the reference's;
    ``kv_err`` the larger of the keys' and the values' median row.  Rows
    rounded to e4m3 read past their limit; a state rounded to bf16 ONCE
    reads ~2^-9, under its limit (a served one is rounded after every
    dispatch, which compounds); the reference's own numbers read 0."""
    rng = np.random.default_rng(8)
    state = rng.standard_normal((2, 3, 4, 4)).astype(np.float32)
    kv = rng.standard_normal((1, 2, 9, 8)).astype(np.float32)
    want = {"state": state, "kv": kv}
    assert REF.store_errors(state[0], kv[0], want) == {"state_err": 0.0,
                                                      "kv_err": 0.0}
    off = kv[0].copy()
    off[0, :4] *= 1.5            # four of nine key rows: under the median
    off[1] *= 1.25               # every value row
    got = REF.store_errors(1.1 * state[0], off, want)
    assert got["state_err"] == pytest.approx(0.1, rel=1e-5)
    assert got["kv_err"] == pytest.approx(0.25, rel=1e-5)
    off[0, 4] *= 1.5             # five of nine
    assert REF.store_errors(state[0], off, want)["kv_err"] == pytest.approx(
        0.5, rel=1e-5)
    rounded = lambda a, e, m: np.asarray(jax.lax.reduce_precision(  # noqa
        jnp.asarray(a), e, m))
    got = REF.store_errors(rounded(state[0], 8, 7), rounded(kv[0], 4, 3),
                           want)
    assert 0.001 < got["state_err"] < 0.003 < REF.STATE_TOLERANCE
    assert got["kv_err"] > REF.KV_TOLERANCE
    with pytest.raises(ValueError, match="served stores"):
        REF.store_errors(state[0], kv[0][:, :5], want)
    # the streams of a length are judged on their median
    streams = [{"logprob_err": np.zeros(2), "argmax_gap": np.zeros(2),
                "state_err": e, "kv_err": 2 * e} for e in (0.1, 0.3, 0.2)]
    got = REF.summary(streams)
    assert (got["state_err"], got["kv_err"]) == (0.2, 0.4)
    assert "state_err" not in REF.summary([{k: s[k] for k in (
        "logprob_err", "argmax_gap")} for s in streams])
    assert 0 < REF.STATE_TOLERANCE < REF.KV_TOLERANCE < REF.TOLERANCE


def test_adapter_fills_the_gdn_leaves_by_the_stated_rule():
    adapter = spec.load_module("models", "qwen3_next")
    key = jax.random.key(1, impl="rbg")
    rule = lambda path, shape: np.asarray(      # noqa: E731
        adapter.fill_rule(path, shape, key, 4))
    a = np.exp(rule("['layer0']['gdn']['a_log']", (4096,)))
    assert 0 < a.min() < 0.1 and 15.9 < a.max() <= 16
    assert 7.5 < a.mean() < 8.5
    assert (rule("['layer0']['gdn']['norm']['scale']", (8,)) == 1).all()
    assert (rule("['layer3']['q_norm']['scale']", (8,)) == 1).all()
    dt = np.asarray(jax.nn.softplus(
        adapter.fill_rule("['layer0']['gdn']['dt_bias']", (4096,), key, 4)))
    assert 1e-3 * 0.99 <= dt.min() < 2e-3 and 5e-2 < dt.max() <= 1e-1 * 1.01
    conv = rule("['layer0']['gdn']['conv_w']", (4, 1024))
    assert 0.45 < np.abs(conv).max() <= 0.5
    w = rule("['layer0']['gdn']['in_qkvz']", (64, 256))
    assert 0.018 < w.std() < 0.022
    sp = adapter.spec_of(QWEN)
    assert (sp.n_experts, sp.experts_held, sp.expert_first) == (512, 128, 0)


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_qwen3next_cell_end_to_end_on_the_cpu():
    """The tiny cell holds experts 4 .. 8 of 16: the served path and the
    reference leave the same twelve out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-qwen3next.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    m = out["metrics"]
    assert "state_kind=gdn" in proc.stdout and "ragged=True" in proc.stdout
    assert "experts=4..+4 of 16" in proc.stdout
    # 4 Gated DeltaNet layers x (4 x 16 x 16 float32 + 3 x 128 bf16);
    # 1 attention layer of 2 KV heads x 32 in bf16
    assert m["ssm.state_bytes_per_lane"]["value"] == 4 * (4096 + 768)
    assert m["kv.bytes_per_token"]["value"] == 2 * 2 * 32 * 2
    assert m["moe.assignments_here_skew"]["value"] < 15
    assert 0 < m["moe.experts_hit_per_step"]["value"] <= 4
    assert 0 < m["sched.mixed_round_share"]["value"] <= 100
    assert "gdn.decode_roofline" not in m     # no TPU trace on a CPU
    assert "ssm.decode_roofline" not in m
