"""Kind ``zaya``: the reference against cases written out by hand, the
rooflines' counts against the issue's reckoning, the new readers on canned
contexts, the new cell's files, and a tiny overlay cell through
``perf/run.py`` end to end on the CPU."""

import json
import os
import subprocess
import sys
from math import erf

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "zaya")
ROOFLINE = spec.load_module("rooflines", "zaya")
ZAYA = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                   "zaya1-8b-l16.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-zaya.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "zaya1-8b-l16.longdoc"
f64 = lambda a: np.asarray(a, np.float64)      # noqa: E731


# ------------------------------------------------------- the reference ----

def _cca(rng, hidden=12, hq=4, g=2, d=4):
    w = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"in_proj": w(hidden, (hq + g) * d + g * d),
            "conv0_w": w(2, (hq + g) * d), "conv0_b": w((hq + g) * d),
            "conv1_w": w(2, hq + g, d, d) * 0.5, "conv1_b": w((hq + g) * d),
            "tau": jnp.asarray([0.9, 1.2], jnp.float32)}


def test_the_mixer_by_hand_in_float64():
    """Steps 1-4 and 6 on five tokens: the depthwise taps, the grouped taps,
    the q-k mean from the PRE-convolution values, the L2 norm with the key
    temperature, the value's shifted half; both convolutions start from
    zeros."""
    rng = np.random.default_rng(1)
    hq, g, d, t = 4, 2, 4, 5
    p = _cca(rng)
    x = rng.standard_normal((t, 12)).astype(np.float32)
    ln = 1 + 0.1 * rng.standard_normal(12).astype(np.float32)
    q, k, v, tails = REF.cca_qkv(jnp.asarray(x), jnp.asarray(ln), p,
                                 n_heads=hq, n_kv_heads=g, eps=1e-5)
    h = f64(x) / np.sqrt((f64(x) ** 2).mean(-1, keepdims=True) + 1e-5) * ln
    proj = h @ f64(p["in_proj"])
    nq, nc = hq * d, (hq + g) * d
    c, v1, v2 = proj[:, :nc], proj[:, nc:nc + 4], proj[:, nc + 4:]
    w0, w1 = f64(p["conv0_w"]), f64(p["conv1_w"])
    a = np.stack([f64(p["conv0_b"]) + w0[1] * c[i]
                  + (w0[0] * c[i - 1] if i else 0) for i in range(t)])
    conv = np.zeros((t, hq + g, d))
    for i in range(t):
        for head in range(hq + g):
            cut = slice(head * d, (head + 1) * d)
            conv[i, head] = f64(p["conv1_b"])[cut] + a[i, cut] @ w1[1, head]
            if i:
                conv[i, head] += a[i - 1, cut] @ w1[0, head]
    tau = f64(p["tau"])
    for i in range(t):
        for kv in range(g):
            kt = c[i, nq + kv * d:nq + (kv + 1) * d]
            heads = range(2 * kv, 2 * kv + 2)
            qts = [c[i, j * d:(j + 1) * d] for j in heads]
            key = conv[i, hq + kv] + (np.mean(qts, 0) + kt) / 2
            np.testing.assert_allclose(
                np.asarray(k)[i, kv],
                tau[kv] * 2.0 * key / np.linalg.norm(key), rtol=2e-5,
                atol=2e-6)
            for j, qt in zip(heads, qts):
                query = conv[i, j] + (qt + kt) / 2
                np.testing.assert_allclose(
                    np.asarray(q)[i, j], 2.0 * query / np.linalg.norm(query),
                    rtol=2e-5, atol=2e-6)
        shifted = v2[i - 1] if i else np.zeros(4)
        np.testing.assert_allclose(np.asarray(v)[i].reshape(-1),
                                   np.concatenate([v1[i], shifted]),
                                   rtol=2e-5, atol=2e-6)
    for got, want in zip(tails, (c[-1], a[-1], v2[-1])):
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-6)
    assert (np.asarray(v)[0, 1] == 0).all()


def test_rope_turns_the_first_columns_alone_and_attention_is_causal_gqa():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((5, 2, 8)), jnp.float32)
    got = np.asarray(REF._rope(x, jnp.arange(5), 100.0, 4))
    np.testing.assert_array_equal(got[0], np.asarray(x)[0])    # position 0
    np.testing.assert_array_equal(got[..., 4:], np.asarray(x)[..., 4:])
    ang = 3 * 100.0 ** (-np.arange(2) / 2)
    x3 = f64(x)[3, 1]
    want = np.concatenate([x3[:2] * np.cos(ang) - x3[2:4] * np.sin(ang),
                           x3[2:4] * np.cos(ang) + x3[:2] * np.sin(ang)])
    np.testing.assert_allclose(got[3, 1, :4], want, rtol=1e-5, atol=1e-6)
    t, hq, g, d = 6, 4, 2, 4
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((t, hq, d), (t, g, d), (t, g, d)))
    wo = jnp.eye(hq * d)
    out, keys = REF.attend(q, k, v, wo, theta=None, rotary=0, block=4)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(k))
    for i in range(t):
        for head in range(hq):
            s = f64(q)[i, head] @ f64(k)[:i + 1, head // 2].T * d ** -0.5
            w = np.exp(s - s.max())
            w /= w.sum()
            np.testing.assert_allclose(
                np.asarray(out)[i, head * d:(head + 1) * d],
                w @ f64(v)[:i + 1, head // 2], rtol=2e-5, atol=2e-6)


def test_router_averages_over_depth_and_chooses_by_probability_plus_bias():
    rng = np.random.default_rng(3)
    w = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    r = {"down": w(10, 6), "down_b": w(6), "gamma": w(6),
         "norm": {"scale": 1 + 0.1 * w(6)}, "w1": w(6, 6), "b1": w(6),
         "w2": w(6, 6), "b2": w(6), "w3": w(6, 5)}
    h, prev = w(9, 10), w(9, 6)
    bias = jnp.asarray([0.0, 0.0, 0.3, 0.0, 0.0], jnp.float32)
    chosen, weight, state = REF.router(h, r, bias, prev, eps=1e-5)
    gelu = np.vectorize(lambda v: 0.5 * v * (1 + erf(v / 2 ** 0.5)))
    s = f64(h) @ f64(r["down"]) + f64(r["down_b"]) + f64(r["gamma"]) * f64(
        prev)
    np.testing.assert_allclose(np.asarray(state), s, rtol=1e-5, atol=1e-6)
    u = s / np.sqrt((s ** 2).mean(-1, keepdims=True) + 1e-5) * f64(
        r["norm"]["scale"])
    z = gelu(gelu(u @ f64(r["w1"]) + f64(r["b1"])) @ f64(r["w2"])
             + f64(r["b2"])) @ f64(r["w3"])
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = (p + f64(bias)).argmax(-1)
    np.testing.assert_array_equal(np.asarray(chosen), want)
    np.testing.assert_allclose(np.asarray(weight), p[np.arange(9), want],
                               rtol=1e-4)
    # layer 0: no state handed on, no gamma read
    first = {k: v for k, v in r.items() if k != "gamma"}
    _c, _w, s0 = REF.router(h, first, bias, None, eps=1e-5)
    np.testing.assert_allclose(np.asarray(s0),
                               f64(h) @ f64(r["down"]) + f64(r["down_b"]),
                               rtol=1e-5, atol=1e-6)


def test_experts_run_the_chosen_one_and_the_last_column_skips():
    rng = np.random.default_rng(4)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    hidden, ff, n_ffn, width = 8, 6, 3, 4
    centred = lambda m: m - m.mean(0, keepdims=True)
    router = {"down": w(hidden, width) * 3, "down_b": w(width),
              "norm": {"scale": jnp.ones(width)}, "w1": w(width, width) * 3,
              "b1": w(width), "w2": centred(w(width, width) * 3),
              "b2": w(width), "w3": centred(w(width, n_ffn + 1) * 9)}
    p = {"ln2": {"scale": jnp.ones(hidden)},
         "moe": {"router": router, "bias": jnp.zeros(n_ffn + 1),
                 "w13": w(n_ffn, hidden, 2 * ff), "w2": w(n_ffn, ff, hidden)}}
    x = w(64, hidden) * 3
    y, state = REF.experts(x, p, None, eps=1e-5)
    h = f64(x) / np.sqrt((f64(x) ** 2).mean(-1, keepdims=True) + 1e-5)
    chosen, weight, _s = REF.router(jnp.asarray(h, jnp.float32), router,
                                    p["moe"]["bias"], None, eps=1e-5)
    chosen, weight = np.asarray(chosen), f64(weight)
    assert set(chosen) == set(range(n_ffn + 1))     # every column is drawn
    silu = lambda v: v / (1 + np.exp(-v))
    for i, e in enumerate(chosen):
        if e == n_ffn:
            want = weight[i] * h[i]
        else:
            w13, w2 = f64(p["moe"]["w13"][e]), f64(p["moe"]["w2"][e])
            want = weight[i] * (silu(h[i] @ w13[:, :ff]) * (h[i] @ w13[:, ff:])
                                ) @ w2
        np.testing.assert_allclose(np.asarray(y)[i], want, rtol=2e-4,
                                   atol=2e-5)
    assert state.shape == (64, width)


def test_hyper_of_reads_the_published_keys_and_the_limits_stand_apart():
    hyper = REF.hyper_of(ZAYA)
    assert hyper == dict(n_layers=16, n_heads=8, n_kv_heads=2,
                         rms_norm_eps=1e-5, rope_theta=5e6, rotary=64)
    assert REF.REFERENCE_STEPS == 32 and REF.REFERENCE_STREAMS == 4
    assert 0 < REF.TOLERANCE < REF.TOLERANCE_SHORT < 1
    assert (REF.tolerance(24), REF.tolerance(5000)) == (
        REF.TOLERANCE_SHORT, REF.TOLERANCE)
    assert 0 < REF.KV_TOLERANCE < REF.KV_ROW_TOLERANCE < 1
    assert 0 < REF.STATE_TOLERANCE < 1
    assert REF.KV_ROW_TOLERANCE < REF.LAYERS_TOLERANCE < 0.8
    assert any(k.startswith("every layer") for k in REF.TOLERANCE_READINGS)
    assert {"bf16", "fp8_pages", "zero_tails"} <= set(REF.TOLERANCE_READINGS)


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.PERF_DIR, "reference", "zaya.py")) as f:
        body = f.read().split('"""', 2)[2]
    assert "tpulab" not in body and "highest" in body


def test_store_errors_read_the_median_row_the_largest_row_and_the_tails():
    """Layer 0 as it was judged, and every layer in forms that a flipped
    expert leaves standing."""
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((3, 2, 40, 8))
    want = {"kv": rows, "state": rng.standard_normal((3, 20))}
    kv = rows * 1.01
    kv[0, 0, 7] = 0                                  # one wrong key
    kv[2, 1, 16] = 0                                 # a wrong value at a seam
    state = want["state"] * np.asarray([1.02, 1.0, 1.5])[:, None]
    got = REF.store_errors(state, kv, want, seams=[16, 32])
    assert got["kv_err"] == pytest.approx(0.01)
    assert got["kv_row_max"] == pytest.approx(1.0)
    assert got["state_err"] == pytest.approx(0.02)
    np.testing.assert_allclose(got["layer_state_err"], [0.02, 0.0, 0.5])
    np.testing.assert_allclose(got["layer_kv_err"], [0.01] * 3)
    np.testing.assert_allclose(got["seam_rows"],
                               [[0.01, 0.01], [0.01, 0.01], [1.0, 0.01]])
    assert REF.store_errors(state, kv, want)["seam_rows"].shape == (3, 0)
    with pytest.raises(ValueError, match="served stores"):
        REF.store_errors(state, kv[:, :, :39], want)
    streams = [dict(logprob_err=np.asarray([0.0, 0.1, 0.2, 0.3]),
                    argmax_gap=np.zeros(4), kv_err=0.1, kv_row_max=0.2,
                    state_err=0.3),
               dict(logprob_err=np.asarray([0.4, 0.5, 0.6, 0.7]),
                    argmax_gap=np.asarray([0.0, 0.0, 1.0, 1.0]), kv_err=0.3,
                    kv_row_max=0.4, state_err=0.5)]
    summed = REF.summary(streams)
    assert summed["logprob_err"] == pytest.approx(0.175)
    assert (summed["kv_err"], summed["kv_row_max"], summed["state_err"]) == (
        pytest.approx(0.2), pytest.approx(0.3), pytest.approx(0.4))
    assert summed["flipped_share"] == 7 / 8
    assert "layers_kv_err" not in summed


def test_every_layer_is_judged_by_what_a_flipped_expert_leaves_standing():
    """Three streams, two layers.  A flip moves ONE stream's tails and its
    rows from the token on: the worst layer's smallest tails over the
    streams, median row over the streams and median row at a seam do not
    see it; a fault that every stream shares moves all three."""
    base = dict(logprob_err=np.zeros(2), argmax_gap=np.zeros(2), kv_err=0.0,
                kv_row_max=0.0, state_err=0.0)
    def stream(tails, rows, seams):
        return dict(base, layer_state_err=np.asarray(tails),
                    layer_kv_err=np.asarray(rows),
                    seam_rows=np.asarray(seams))
    clean = stream([0.01, 0.02], [0.01, 0.03], [[0.01, 0.01], [0.02, 0.02]])
    flipped = stream([0.01, 0.9], [0.01, 0.6], [[0.01, 0.01], [0.02, 0.8]])
    got = REF.summary([clean, flipped, clean])
    assert got["layers_state_err"] == pytest.approx(0.02)
    assert got["layers_kv_err"] == pytest.approx(0.03)
    assert got["layers_seam_err"] == pytest.approx(0.02)
    lost = stream([0.01, 1.0], [0.01, 0.03], [[0.01, 0.01], [0.9, 0.9]])
    got = REF.summary([lost, lost, lost])
    assert got["layers_state_err"] == pytest.approx(1.0)
    assert got["layers_seam_err"] == pytest.approx(0.9)
    # a prompt of one chunk has no seam: the number is left out
    whole = stream([0.01, 0.02], [0.01, 0.03], np.zeros((2, 0)))
    got = REF.summary([whole, whole])
    assert "layers_seam_err" not in got and got["layers_kv_err"] == 0.03


# -------------------------------------------------------- the rooflines ----

def test_parameter_cache_and_state_counts_are_the_issues():
    """ISSUE 54's own count: CCA 5.58 M, the router 0.66 M, an expert
    12,582,912, 3,858.4 M in the matrices (the norm scales left out) = 7.72
    GB, 16,384 B of K/V rows a token, 86,016 B of tails a lane."""
    assert ROOFLINE.latent_widths(ZAYA) == (1024, 256)
    assert ROOFLINE.attention_params(ZAYA) == (
        5_242_880 + 3_840 + 328_960) == 5_575_680
    assert ROOFLINE.router_params(ZAYA) == (
        2048 * 256 + 3 * 256 + 2 * (65536 + 256) + 256 * 17 + 17) == 661_009
    assert ROOFLINE.expert_params(ZAYA) == 12_582_912
    assert ROOFLINE.head_params(ZAYA) == 537_133_056
    assert ROOFLINE.outside_expert_params(ZAYA) == 16 * (
        5_575_680 + 661_009 + 16_384)
    assert ROOFLINE.model_params(ZAYA) == (
        16 * (5_575_680 + 661_009 + 16_384 + 16 * 12_582_912) + 537_133_056)
    assert 7.71e9 < 2 * ROOFLINE.model_params(ZAYA) < 7.72e9
    assert ROOFLINE.kv_bytes_per_token(ZAYA) == 16_384
    assert ROOFLINE.state_bytes_per_lane(ZAYA) == 86_016


def test_step_bytes_and_round_flops_are_the_issues_table():
    """A decode step of 32 lanes at 7 k keys with all 16 experts hit: 7.7 GB
    of weights and 3.67 GB of K/V rows (0.23 GB a layer beside 0.40 GB of
    experts); a round's pairs cost ``4 x 1024`` operations a layer."""
    got = ROOFLINE.decode_step_bytes(ZAYA, 32, 16, 7000)
    weights = 2 * (ROOFLINE.outside_expert_params(ZAYA)
                   + 16 * 16 * 12_582_912 + 537_133_056)
    assert got == weights + 32 * 7000 * 16_384 + 2 * 32 * 86_016
    assert 7.7e9 < weights < 7.73e9 and 11.3e9 < got < 11.5e9
    assert ROOFLINE.decode_kv_bytes(ZAYA, 32, 7000) / 16 == pytest.approx(
        0.229e9, rel=0.01)
    assert 16 * 12_582_912 * 2 == pytest.approx(0.403e9, rel=0.01)
    assert ROOFLINE.decode_step_bytes(ZAYA, 0, 0, 0) == 2 * (
        ROOFLINE.outside_expert_params(ZAYA) + 537_133_056)
    assert ROOFLINE.round_bytes(ZAYA, 3, 5000) == (
        2 * ROOFLINE.model_params(ZAYA) + 3 * 5000 * 16_384 + 2 * 3 * 86_016)
    assert ROOFLINE.attention_pair_flops(ZAYA) == 4096
    assert ROOFLINE.round_flops(ZAYA, 544, 0, 0, 0) == (
        2 * 544 * ROOFLINE.outside_expert_params(ZAYA))
    assert ROOFLINE.round_flops(ZAYA, 0, 10, 0, 0) == 20 * 12_582_912
    assert ROOFLINE.round_flops(ZAYA, 0, 0, 1000, 0) == 1000 * 16 * 4096
    assert ROOFLINE.round_flops(ZAYA, 0, 0, 0, 3) == 6 * 537_133_056


# -------------------------------------------------------- the readers ----

class _Cell:
    config = ZAYA

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None, cca=True):
    def moe(scale):
        rows = [[scale * (1 + (e % 3)) for e in range(17)] for _ in range(16)]
        return {"expert_layers": list(range(16)), "assignments": rows,
                "zero_first": 16, "zero_columns": 1, "first": 0, "held": 16,
                "assignments_here": [sum(r[:16]) for r in rows],
                "decode_steps": 10 * scale, "experts_hit": 16 * 150 * scale}
    pool = {"n_pages": 20481, "page_size": 16,
            "hbm_bytes": 20481 * 16 * 16384}

    def dispatch(scale):
        # 30 lanes a decode step at 7,000 keys a lane; a round of 512 prompt
        # tokens and 20 decode rows: 21 lanes at 6,000 keys a lane
        return {"decode_block_steps": 100 * scale, "mixed_tokens":
                40 * 532 * scale, "mixed_rows": 40 * 544 * scale, "kinds": {
                    "decode": 50 * scale, "mixed": 40 * scale, "verify": 0},
                "round_attn_pairs": 40 * 2_000_000 * scale,
                "lane_work": {
                    "decode": {"passes": 3000 * scale, "rows": 3000 * scale,
                               "keys": 3000 * 7000 * scale},
                    "round": {"passes": 840 * scale, "rows": 40 * 532 * scale,
                              "keys": 840 * 6000 * scale}}}

    def counters(scale):
        out = {"moe": moe(scale), "pool": pool, "dispatch": dispatch(scale),
               "state": {"kind": "cca", "bytes_per_lane": 86016}}
        if cca:
            out["cca"] = {"taps": [2, 2], "state_bytes_per_lane": 86016,
                          "kv_bytes_per_token": 16384,
                          "rows": {"round": 40 * 532 * scale,
                                   "decode": 3000 * scale}}
        return out
    return {"cell": _Cell, "trace": trace, "gauges": [], "say": None,
            "counters_before": counters(1), "counters_after": counters(3)}


TRACE = {"modules": {
    "jit_paged_decode_block_k2": {"durations_s": [0.034, 0.036]},
    "jit_paged_decode_block_k1": {"durations_s": [0.018]},
    "jit_paged_mixed_step": {"durations_s": [0.021, 0.023]}}}
NEW = ("cca.decode_roofline", "cca.round_mfu", "cca.decode_kv_share")


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx()
    assert read("kv.bytes_per_token", ctx) == 16384
    assert read("ssm.state_bytes_per_lane", ctx) == 86016
    assert read("moe.experts_hit_per_step", ctx) == 15
    assert read("moe.zero_expert_share", ctx) == pytest.approx(
        100 * 2 / 33)
    assert read("cca.decode_kv_share", ctx) == pytest.approx(
        100 * ROOFLINE.decode_kv_bytes(ZAYA, 30, 7000)
        / ROOFLINE.decode_step_bytes(ZAYA, 30, 15, 7000))
    for name in ("cca.decode_roofline", "cca.round_mfu"):
        assert read(name, ctx) is None                          # no trace
    # a model without CCA (or the parent's program, which has no such
    # group): nothing to read, no error
    plain = _ctx(TRACE, cca=False)
    bare = {"cell": _Cell, "trace": TRACE, "gauges": [],
            "counters_before": {"dispatch": {}},
            "counters_after": {"dispatch": {}}}
    for name in NEW:
        assert read(name, plain) is None and read(name, bare) is None
    # a traced slice without a decode block, or without a round
    only_rounds = _ctx({"modules": {"jit_paged_mixed_step": {
        "durations_s": [0.03]}}})
    assert read("cca.decode_roofline", only_rounds) is None
    only_blocks = _ctx({"modules": {"jit_paged_decode_block_k2": {
        "durations_s": [0.03]}}})
    assert read("cca.round_mfu", only_blocks) is None


def test_shares_are_bytes_and_flops_over_the_peaks_over_mean_time(
        monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    ctx = _ctx(TRACE)
    said = []
    ctx["say"] = said.append
    read = lambda name: spec.load_module("layer_metrics", name).read(ctx)
    step = (0.034 + 0.036 + 0.018) / (2 + 2 + 1)
    assert read("cca.decode_roofline") == pytest.approx(
        100 * ROOFLINE.decode_step_bytes(ZAYA, 30, 15, 7000) / 819e9 / step)
    work = spec.load_module("layer_metrics", "scmoe.round_mfu").round_work(
        ctx)
    tokens, expert_rows, pairs, lanes = work
    assert (tokens, pairs, lanes) == (532, 2_000_000, 21)
    # the skip column's assignments cost no product
    assert expert_rows == pytest.approx(
        2 * 16 * 31 * (2 * 40 * 532) / (2 * 40 * 532 + 2 * 3000) / 80)
    assert read("cca.round_mfu") == pytest.approx(
        100 * ROOFLINE.round_flops(ZAYA, *work) / 197e12 / 0.022)
    floors = spec.load_module("layer_metrics", "cca.round_mfu").bounds(ctx)
    assert floors["bytes_s"] == pytest.approx(
        ROOFLINE.round_bytes(ZAYA, 21, 6000) / 819e9)
    assert said and "at the HBM bandwidth" in said[0]
    assert 0 < read("cca.decode_roofline") < 100
    assert 0 < read("cca.round_mfu") < 100


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "zaya1-8b-l16", "longdoc-closed-c32")
    c = cell.config
    assert c["kind"] == "zaya"
    assert c["reduced"].keys() == {"num_hidden_layers"}
    assert (c["num_hidden_layers"], c["num_experts"],
            c["num_experts_per_tok"], c["router_hidden_size"],
            c["vocab_size"], c["cca_time0"], c["cca_time1"]) == (
                16, 16, 1, 256, 262272, 2, 2)
    assert {"assumed", "departures", "stands_for", "layout",
            "kv_bytes_per_token", "state_bytes_per_lane"} <= set(c)
    why = c["reduced"]["num_hidden_layers"]["why"]
    assert "3,858.5 M" in why and "7.72 GB" in why
    assert "16,384 B" in c["kv_bytes_per_token"]
    assert {"convolution padding", "value halves over the KV heads",
            "key temperature", "router input", "depth averaging",
            "router MLP", "skip column", "residual scaling", "experts",
            "torch_dtype", "weights", "convolution weights",
            "router weights"} <= set(c["assumed"])
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 16384,
                                      "page_size": 16, "pool_tokens": 327680}
    assert cell.traffic["reference_prompt_lens"] == [24, 5000]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"moe.experts_hit_per_step", "moe.zero_expert_share",
                       "moe.expert_load_max_over_mean", "kv.bytes_per_token",
                       "ssm.state_bytes_per_lane",
                       "step.mixed_round_ms"} <= names
    assert not {"gdn.decode_roofline", "ssm.decode_roofline",
                "mhc.decode_roofline", "scmoe.decode_roofline",
                "step.decode_weight_roofline"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "zaya")
    from tpulab.models.spec import zaya_spec
    sp = zaya_spec(c)
    assert (sp.n_layers, sp.n_experts, sp.zero_experts, sp.top_k,
            sp.cca_taps, sp.state_kind) == (16, 17, 1, 1, (2, 2), "cca")
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "ZAYA1-8B")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"])
        assert c["source"] == row["source_url"]


def test_the_benchmark_gained_one_configuration_one_cell_three_metrics():
    """By NAME, not by place: the next PR appends behind these entries
    (``test_xing4_0`` and ``test_host_stall_readers`` hold theirs by place
    and fail since this one did)."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    named = lambda key, name: next(x for x in bench[key] if x["name"] == name)
    cell = named("workloads", CELL)
    assert cell == {"name": CELL, "config": "zaya1-8b-l16",
                    "traffic": "longdoc-closed-c32", "chips": 1,
                    "why": cell["why"]}
    assert named("configs", "zaya1-8b-l16")["reduced"] == [
        "num_hidden_layers"]
    for name in NEW:
        m = named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    # ``step.decode_ms`` too: every traced tail of the cell held decode
    # blocks (``cca.decode_roofline`` reads the same programs)
    for name in ("moe.expert_load_max_over_mean", "moe.experts_hit_per_step",
                 "moe.zero_expert_share", "kv.bytes_per_token",
                 "ssm.state_bytes_per_lane", "step.decode_ms"):
        assert CELL in named("per_layer", name)["workloads"]
    assert all(len(x["why"]) <= 200
               for x in bench["configs"] + bench["workloads"])


def test_the_mix_fits_the_pool_and_no_operation_can_fail():
    """longdoc-closed-c32: prompts 2093-12288, outputs 402-1466: the pool
    holds the whole set at once (no preemption: ~239 k of 327,680 tokens,
    three quarters) and max_len the longest pair."""
    from harness.sizes import size_pairs
    traffic = spec.load_cell(CELL).traffic
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (2093, 12288)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (402, 1466)
    eng = traffic["engine"]
    assert 0.7 < pairs.sum() / eng["pool_tokens"] < 0.76
    assert pairs.sum(1).max() <= eng["max_len"]
    assert set(traffic) >= {"generator", "concurrency", "set_size",
                            "prompt_len", "output_len", "pairing_seed",
                            "channels", "ramp_max_s"}
    assert (traffic["prompt_len"], traffic["output_len"]) == (
        {"dist": "lognormal", "median": 6144, "sigma": 0.5, "min": 2048,
         "max": 12288},
        {"dist": "lognormal", "median": 768, "sigma": 0.3, "min": 256,
         "max": 2048})


def test_adapter_draws_what_normal_002_would_switch_off_by_the_programs_rule():
    adapter = spec.load_module("models", "zaya")
    tiny = spec.load_json(os.path.join(
        spec.PERF_DIR, "tests", "cells", "configs", "tiny-zaya.json"))
    from functools import partial

    from tpulab.models.spec import init_params, zaya_spec
    sp = zaya_spec(tiny)
    tree = jax.eval_shape(partial(init_params, sp, 256, 0))
    params = adapter.make_weights(tree, 2**31 + 5)
    std = lambda x: float(np.asarray(x, np.float32).std())
    layer = params["layer1"]
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(params))
    assert 0.017 < std(layer["cca"]["in_proj"]) < 0.023
    assert 0.017 < std(layer["moe"]["w13"]) < 0.023
    assert (np.asarray(layer["ln1"]["scale"], np.float32) == 1).all()
    assert 0.5 < std(layer["cca"]["conv0_w"]) < 0.9
    assert 0.7 * 32 ** -0.5 < std(layer["cca"]["conv1_w"]) < 1.3 * 32 ** -0.5
    tau = np.asarray(layer["cca"]["tau"], np.float32)
    assert (0.79 < tau).all() and (tau < 1.21).all()
    r = layer["moe"]["router"]
    assert 0.7 * 64 ** -0.5 < std(r["down"]) < 1.3 * 64 ** -0.5
    assert 0.3 < std(r["w3"]) < 0.7
    assert np.abs(np.asarray(r["w3"], np.float32).mean(0)).max() < 0.02
    gamma = np.asarray(r["gamma"], np.float32)
    assert (0.49 < gamma).all() and (gamma < 1.01).all()
    assert "gamma" not in params["layer0"]["moe"]["router"]
    s_r = np.asarray(layer["res_attn"]["s_r"], np.float32)
    assert (0.79 < s_r).all() and (s_r < 1.21).all()
    assert std(layer["res_ffn"]["b_o"]) < 0.03
    assert "lm_head" not in params


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_zaya_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-zaya.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    assert "kv_row_max" in proc.stdout and "state_err" in proc.stdout
    assert "layers_seam_err" in proc.stdout       # a prompt of two chunks
    got = line["metrics"]
    assert got["kv.bytes_per_token"]["value"] == 3 * 2 * 32 * 2
    assert got["ssm.state_bytes_per_lane"]["value"] == 3 * 336 * 2
    assert got["compiles_in_window.lm"]["value"] == 0
    assert 0 < got["moe.experts_hit_per_step"]["value"] <= 16
    assert 0 <= got["moe.zero_expert_share"]["value"] < 100
    assert 0 < got["cca.decode_kv_share"]["value"] < 100
    assert "rehearsal" in line


# ------------------------------- faults planted under the harness's check ----

class _Direct:
    """``check_reference``'s client without the RPC: one greedy stream on
    the engine itself."""

    def __init__(self, engine):
        self.engine = engine

    def call(self, request):
        one = request["requests"][0]
        tokens, logprobs = self.engine.submit(
            one["prompt"], one["steps"], logprobs=True).result(timeout=600)
        return {"results": [{"ok": True, "tokens": tokens,
                             "logprobs": logprobs, "error": None}]}


@pytest.mark.parametrize("fault", ["none", "zero_tails_past_layer_0",
                                   "state_slot_1_past_layer_0"])
def test_the_harness_own_correct_under_a_planted_fault(fault, monkeypatch):
    """``Adapter.check_reference`` itself, on the tiny overlay cell (prompts
    of 5 and of 600 tokens: two chunks), over step programs with a fault
    planted in LAYERS 1-2 alone, where layer 0's three numbers and the
    logits' lower quartile see nothing: every chunk of a round started from
    zero tails; the lane-state store indexed by a wrong layer.  The chip's
    readings of the same faults at the cell's size are the reference's
    ``TOLERANCE_READINGS``."""
    from functools import partial

    from tpulab.engine import paged_steps as ps
    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.spec import init_params
    cell = spec.load_cell("tiny-zaya.closed", CELLS)
    said = []
    models = spec.load_module("models", "zaya")
    adapter = models.build(cell, 2**31 + 17, said.append)
    window, cca = ps._segment_window, ps._cca_qkv

    def zero_window(x, k, tails, seg, live=None, fresh=None):
        if seg.get("row_seg") is not None:
            tails = jnp.zeros_like(tails)
        return window(x, k, tails, seg, live, fresh)

    def zero_past_0(sp, p, at, *rest):
        monkeypatch.setattr(ps, "_segment_window",
                            zero_window if at else window)
        return cca(sp, p, at, *rest)

    def slot_1_past_0(sp, p, at, *rest):
        return cca(sp, p, min(at, 1), *rest)

    patch = {"zero_tails_past_layer_0": zero_past_0,
             "state_slot_1_past_layer_0": slot_1_past_0}.get(fault)
    if patch:
        monkeypatch.setattr(ps, "_cca_qkv", patch)
    ps._JIT_MEMO.clear()           # a memoised program is the unpatched one
    tree = jax.eval_shape(partial(init_params, adapter.spec,
                                  adapter.hyper["vocab"], 0))
    adapter.params = models.make_weights(tree, adapter.seed)
    sz = cell.traffic["engine"]
    adapter.engine = cb = ContinuousBatcher(
        adapter.params, adapter.spec.n_heads, adapter.spec.n_layers,
        spec=adapter.spec, lanes=int(sz["lanes"]),
        max_len=int(sz["max_len"]), page_size=int(sz["page_size"]),
        n_pages=int(sz["pool_tokens"]) // int(sz["page_size"]) + 1,
        compute_dtype=jnp.bfloat16)
    try:
        agrees = adapter.check_reference(_Direct(cb))
    finally:
        cb.shutdown()
        ps._JIT_MEMO.clear()
    long = next(line for line in said if "prompts of 600" in line)
    assert agrees == (fault == "none"), said
    # layer 0's own numbers stand in every case: the fault lies deeper
    for name in ("kv_err", "kv_row_max", "state_err"):
        got = float(long.split(f" {name}=")[1].split(" ")[0])
        assert got < 0.01, (name, got)
    deep = {name: float(long.split(f" {name}=")[1].split(" ")[0])
            for name in ("layers_state_err", "layers_seam_err")}
    if fault == "zero_tails_past_layer_0":
        assert deep["layers_seam_err"] > 0.6 > 0.1 > deep["layers_state_err"]
    elif fault == "state_slot_1_past_layer_0":
        assert deep["layers_state_err"] > 0.9
    else:
        assert max(deep.values()) < 0.1
