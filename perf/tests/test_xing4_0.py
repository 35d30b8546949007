"""Kind ``xing4_0``: the reference against cases written out by hand, the
rooflines' counts against the issue's reckoning, the new readers on canned
contexts, the new cell's files, and a tiny overlay cell through
``perf/run.py`` end to end on the CPU."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "xing4_0")
ROOFLINE = spec.load_module("rooflines", "xing4_0")
XING = spec.load_json(os.path.join(spec.PERF_DIR, "configs", "xing4-l6.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-xing4.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "xing4-l6.rag"


# ------------------------------------------------------- the reference ----

def test_yarn_frequencies_by_hand():
    """d 64, theta 10000, factor 64, 4,096 original positions, beta 32 / 1:
    the ramp runs over pairs 10..23; ``mscale`` = 0.1 ln 64 + 1."""
    assert REF.yarn_bounds(64, 1e4, 4096, 32, 1) == (10, 23)
    inv = REF.yarn_inv_freq(64, 1e4, 64, 4096, 32, 1)
    base = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 64, rtol=1e-12)
    assert inv[15] == pytest.approx(base[15] * ((1 - 5 / 13) + 5 / 13 / 64))
    assert REF.yarn_mscale(64, 1) == pytest.approx(1.41589, abs=1e-5)
    assert REF.yarn_mscale(64, 1) ** 2 == pytest.approx(2.0047, abs=1e-4)
    assert REF.yarn_mscale(1, 1) == 1.0 and REF.yarn_mscale(64, 0) == 1.0


def test_rope_turns_rotate_half_pairs_at_the_given_frequencies():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((5, 2, 8)), jnp.float32)
    inv = jnp.asarray([1.0, 0.1, 0.01, 0.001], jnp.float32)
    got = np.asarray(REF._rope(x, jnp.arange(5), inv, 1.0))
    np.testing.assert_array_equal(got[0], np.asarray(x)[0])   # position 0
    ang = 3 * np.asarray(inv, np.float64)
    x3 = np.asarray(x)[3, 1]
    want = np.concatenate([x3[:4] * np.cos(ang) - x3[4:] * np.sin(ang),
                           x3[4:] * np.cos(ang) + x3[:4] * np.sin(ang)])
    np.testing.assert_allclose(got[3, 1], want, rtol=1e-5, atol=1e-6)


def _hc(rng, n=4, c=6):
    w = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"norm": {"scale": 1 + 0.1 * w(n * c)},
            "phi": w(n * c, 2 * n + n * n) * (n * c) ** -0.5,
            "alpha": jnp.asarray([0.7, 1.3, 0.9], jnp.float32),
            "bias": w(2 * n + n * n)}


def test_hyper_connection_by_hand_in_float64():
    """The read side and the write side of one sublayer on five tokens."""
    rng = np.random.default_rng(5)
    hc = _hc(rng)
    X = rng.standard_normal((5, 4, 6)).astype(np.float32)
    f = rng.standard_normal((5, 6)).astype(np.float32)
    u, M, post = REF.hyper_connection(jnp.asarray(X), hc, iters=20, eps=1e-6,
                                      clamp=(-30.0, 30.0))
    out = np.asarray(REF.write_back(jnp.asarray(X), M, post, jnp.asarray(f)))
    f64 = lambda a: np.asarray(a, np.float64)
    sig = lambda v: 1 / (1 + np.exp(-v))
    for t in range(5):
        flat = f64(X[t]).reshape(-1)
        v = flat / np.sqrt((flat ** 2).mean() + 1e-6) * f64(
            hc["norm"]["scale"])
        proj = v @ f64(hc["phi"])
        b = f64(hc["bias"])
        pre = sig(0.7 * proj[:4] + b[:4])
        h_post = 2 * sig(1.3 * proj[4:8] + b[4:8])
        S = np.clip(0.9 * proj[8:] + b[8:], -30, 30).reshape(4, 4)
        m = np.exp(S)
        for _ in range(20):
            m = m / (m.sum(1, keepdims=True) + 1e-6)
            m = m / (m.sum(0, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(u)[t], pre @ f64(X[t]),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(M)[t], m, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(
            out[t], m @ f64(X[t]) + np.outer(h_post, f[t]), rtol=2e-5,
            atol=2e-5)
    np.testing.assert_allclose(np.asarray(M).sum(1), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(M).sum(2), 1.0, atol=5e-3)


def test_streams_start_as_copies_and_end_as_their_sum():
    """One layer with every map forced (``h_pre`` 1/4, ``h_post`` 1, ``H_res``
    the identity) is the plain residual on four equal streams; the head
    reads the norm of their sum."""
    rng = np.random.default_rng(6)
    n, c = 4, 8
    bias = jnp.concatenate([jnp.full((n,), -math.log(3.0)), jnp.zeros((n,)),
                            (60.0 * jnp.eye(n) - 30.0).reshape(-1)])
    hc = dict(_hc(rng, n, c), phi=jnp.zeros((n * c, 24)), bias=bias)
    x = jnp.asarray(rng.standard_normal((3, c)), jnp.float32)
    X = jnp.broadcast_to(x[:, None], (3, n, c))
    u, M, post = REF.hyper_connection(X, hc, iters=20, eps=1e-6,
                                      clamp=(-30.0, 30.0))
    np.testing.assert_allclose(np.asarray(u), np.asarray(x), rtol=1e-5)
    out = np.asarray(REF.write_back(X, M, post, 2 * x))
    for i in range(n):
        np.testing.assert_allclose(out[:, i], 3 * np.asarray(x), rtol=1e-5,
                                   atol=1e-6)
    head = jnp.asarray(rng.standard_normal((c, 5)), jnp.float32)
    scale = jnp.ones((c,), jnp.float32)
    got = np.asarray(REF._head(X, scale, head, eps=1e-6))
    xs = 4 * np.asarray(x, np.float64)
    want = xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-6) @ np.asarray(
        head, np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_router_chooses_by_score_plus_bias_and_renormalises_times_two():
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.standard_normal((9, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8), jnp.float32)
    chosen, weights = REF.route(h, w, bias, top_k=4, scale=2.0, norm=True)
    s = 1 / (1 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(
        w, np.float64))))
    want = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(chosen), want)
    picked = np.take_along_axis(s, want, -1)
    np.testing.assert_allclose(
        np.asarray(weights), 2 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    # the bias moved a choice
    assert (np.sort(want, -1) != np.sort(np.argsort(-s, -1)[:, :4], -1)).any()


def test_hyper_of_reads_the_published_keys():
    hyper = REF.hyper_of(XING)
    assert (hyper["n_layers"], hyper["rope_dim"], hyper["nope_dim"]) == (
        6, 64, 128)
    assert hyper["yarn"] == (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert (hyper["hc_mult"], hyper["hc_sinkhorn_iters"], hyper["hc_eps"],
            hyper["hc_clamp"]) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (hyper["top_k"], hyper["routed_scaling_factor"],
            hyper["norm_topk_prob"]) == (4, 2.0, True)
    assert (hyper["rms_norm_eps"], hyper["rope_theta"]) == (1e-6, 1e4)
    assert REF.REFERENCE_STEPS == 32 and REF.REFERENCE_STREAMS == 4
    assert 0 < REF.TOLERANCE < REF.TOLERANCE_SHORT < 1
    assert (REF.tolerance(24), REF.tolerance(2000)) == (
        REF.TOLERANCE_SHORT, REF.TOLERANCE)
    assert {"bf16", "fp8_latent", "bf16_coefficients"} <= set(
        REF.TOLERANCE_READINGS)


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.PERF_DIR, "reference", "xing4_0.py")) as f:
        body = f.read().split('"""', 2)[2]
    assert "tpulab" not in body and "highest" in body


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
    """ISSUE 50's own count: an attention 28.41 M, a hyper-connection
    0.36 M a sublayer, the dense FFN 99.09 M, an expert 11.01 M, the dense
    layer 128.2 M, an expert layer 745.0 M, 4,792.8 M parameters = 9.59 GB,
    6,912 B of latent rows a token, 28,672 B of streams a row."""
    assert ROOFLINE.attention_params(XING) == (
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    ) == 28_409_856
    assert ROOFLINE.mhc_maps(XING) == 24
    assert ROOFLINE.mhc_params(XING) == 14336 * 24 + 14336 + 27 == 358_427
    assert ROOFLINE.dense_ffn_params(XING) == 3 * 3584 * 9216 == 99_090_432
    assert ROOFLINE.expert_params(XING) == 3 * 3584 * 1024 == 11_010_048
    assert (ROOFLINE.sublayers(XING), ROOFLINE.expert_layers(XING)) == (12, 5)
    outside = ROOFLINE.outside_expert_params(XING)
    assert outside == (6 * (28_409_856 + 2 * 358_427) + 99_090_432
                       + 5 * (3584 * 64 + 11_010_048))
    assert ROOFLINE.model_params(XING) == (
        outside + 5 * 64 * 11_010_048 + 2 * 131072 * 3584
    ) == 939_524_096 + 128_217_142 + 5 * 745_009_206
    assert 9.58e9 < 2 * ROOFLINE.model_params(XING) < 9.59e9
    assert ROOFLINE.kv_bytes_per_token(XING) == 6912
    assert ROOFLINE.stream_bytes_per_row(XING) == 28672


def test_step_bytes_and_round_flops_are_the_issues_table():
    """A decode step of 32 lanes at ~3 k keys with 55.5 of 64 experts hit a
    layer: ~7.7 GB of weights (ISSUE 50's reckoning), 0.66 GB of latent
    rows, 22 MB of streams; a round of 544 rows: the hyper-connections add
    2 n C (2 n + n^2) + 2 n^2 C + 4 n C operations a row a sublayer."""
    got = ROOFLINE.decode_step_bytes(XING, 32, 55.5, 3000)
    weights = 2 * (ROOFLINE.outside_expert_params(XING)
                   + 5 * 55.5 * 11_010_048 + 131072 * 3584)
    assert 7.6e9 < weights < 7.8e9
    assert got == weights + 32 * 3000 * 6912 + 2 * 32 * 12 * 28672
    assert 8.3e9 < got < 8.5e9
    assert ROOFLINE.decode_step_bytes(XING, 0, 0, 0) == 2 * (
        ROOFLINE.outside_expert_params(XING) + 131072 * 3584)
    whole = ROOFLINE.round_bytes(XING, 20, 3000, rows=532)
    assert whole == (2 * (ROOFLINE.model_params(XING) - 131072 * 3584)
                     + 20 * 3000 * 6912 + 2 * 532 * 12 * 28672)
    assert ROOFLINE.attention_pair_flops(XING) == 2 * 32 * (576 + 512)
    per_row = 2 * 14336 * 24 + 2 * 16 * 3584 + 4 * 4 * 3584
    cost = ROOFLINE.mhc_cost(XING, 544)
    assert cost["flops"] == 544 * 12 * per_row
    assert cost["bytes"] == 12 * (2 * 544 * 28672 + 2 * 358_427)
    # a round of 512 rows passes 14.7 MB of streams a pass, as ISSUE 50 says
    assert 512 * 28672 == 14_680_064
    rows = ROOFLINE.round_flops(XING, 544, 0, 0, 0)
    assert rows == 544 * (2 * ROOFLINE.outside_expert_params(XING)
                          + 12 * (2 * 16 * 3584 + 16 * 3584))
    # phi's product is counted with the parameters, the sums beside it
    assert rows == (2 * 544 * (ROOFLINE.outside_expert_params(XING)
                               - 12 * 14336 * 24) + cost["flops"])
    assert ROOFLINE.round_flops(XING, 0, 10, 0, 0) == 20 * 11_010_048
    assert ROOFLINE.round_flops(XING, 0, 0, 1000, 0) == (
        1000 * 6 * 2 * 32 * 1088)
    assert ROOFLINE.round_flops(XING, 0, 0, 0, 3) == 6 * 131072 * 3584


# -------------------------------------------------------- the readers ----

class _Cell:
    config = XING

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None, mhc=True):
    def moe(scale):
        rows = [[scale * (1 + (e % 3)) for e in range(64)] for _ in range(5)]
        return {"expert_layers": [1, 2, 3, 4, 5], "assignments": rows,
                "zero_first": 64, "zero_columns": 0, "first": 0, "held": 64,
                "assignments_here": [sum(r) for r in rows],
                "decode_steps": 10 * scale, "experts_hit": 5 * 550 * scale}
    pool = {"n_pages": 20481, "page_size": 16,
            "hbm_bytes": 20481 * 16 * 7680}

    def dispatch(scale):
        # 30 lanes a decode step at 3,000 keys a lane; a round of 512 prompt
        # tokens and 20 decode rows: 21 lanes at 2,500 keys a lane
        return {"decode_block_steps": 100 * scale, "mixed_tokens":
                40 * 532 * scale, "mixed_rows": 40 * 544 * scale, "kinds": {
                    "decode": 50 * scale, "mixed": 40 * scale, "verify": 0},
                "round_attn_pairs": 40 * 900_000 * scale,
                "lane_work": {
                    "decode": {"passes": 3000 * scale, "rows": 3000 * scale,
                               "keys": 3000 * 3000 * scale},
                    "round": {"passes": 840 * scale, "rows": 40 * 532 * scale,
                              "keys": 840 * 2500 * scale}}}

    def counters(scale):
        out = {"moe": moe(scale), "pool": pool, "dispatch": dispatch(scale)}
        if mhc:
            out["mhc"] = {"streams": 4, "sublayers": 12, "sinkhorn_iters": 20,
                          "stream_bytes_per_row": 28672,
                          "rows": {"round": 40 * 532 * scale,
                                   "decode": 3000 * scale}}
        return out
    return {"cell": _Cell, "trace": trace, "gauges": [], "say": None,
            "counters_before": counters(1), "counters_after": counters(3)}


TRACE = {"modules": {
    "jit_paged_decode_block_k2": {"durations_s": [0.026, 0.028]},
    "jit_paged_decode_block_k1": {"durations_s": [0.014]},
    "jit_paged_mixed_step": {"durations_s": [0.033, 0.035]}}}


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx()
    assert read("mhc.stream_bytes_per_row", ctx) == 28672
    assert read("kv.bytes_per_token", ctx) == 7680
    assert read("moe.experts_hit_per_step", ctx) == 55
    assert read("moe.expert_load_max_over_mean", ctx) == pytest.approx(
        3 / (127 / 64))
    for name in ("mhc.decode_roofline", "mhc.round_mfu"):
        assert read(name, ctx) is None                          # no trace
    # a model without hyper-connections (or the parent's program, which has
    # no such group): nothing to read, no error
    plain = _ctx(TRACE, mhc=False)
    for name in ("mhc.decode_roofline", "mhc.round_mfu",
                 "mhc.stream_bytes_per_row"):
        assert read(name, plain) is None
    bare = {"cell": _Cell, "trace": TRACE, "gauges": [],
            "counters_before": {"dispatch": {}},
            "counters_after": {"dispatch": {}}}
    for name in ("mhc.decode_roofline", "mhc.round_mfu",
                 "mhc.stream_bytes_per_row"):
        assert read(name, bare) is None
    # a traced slice without a decode block, or without a round
    only_rounds = _ctx({"modules": {"jit_paged_mixed_step": {
        "durations_s": [0.03]}}})
    assert read("mhc.decode_roofline", only_rounds) is None
    only_blocks = _ctx({"modules": {"jit_paged_decode_block_k2": {
        "durations_s": [0.03]}}})
    assert read("mhc.round_mfu", only_blocks) is None


def test_shares_are_bytes_and_flops_over_the_peaks_over_mean_time(
        monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    ctx = _ctx(TRACE)
    said = []
    ctx["say"] = said.append
    read = lambda name: spec.load_module("layer_metrics", name).read(ctx)
    step = (0.026 + 0.028 + 0.014) / (2 + 2 + 1)
    assert read("mhc.decode_roofline") == pytest.approx(
        100 * ROOFLINE.decode_step_bytes(XING, 30, 55, 3000) / 819e9 / step)
    work = spec.load_module("layer_metrics", "scmoe.round_mfu").round_work(
        ctx)
    tokens, expert_rows, pairs, lanes = work
    assert (tokens, pairs, lanes) == (532, 900_000, 21)
    assert expert_rows == pytest.approx(
        2 * 5 * 127 * (2 * 40 * 532) / (2 * 40 * 532 + 2 * 3000) / 80)
    assert read("mhc.round_mfu") == pytest.approx(
        100 * ROOFLINE.round_flops(XING, *work) / 197e12 / 0.034)
    floors = spec.load_module("layer_metrics", "mhc.round_mfu").bounds(ctx)
    assert floors["bytes_s"] == pytest.approx(
        ROOFLINE.round_bytes(XING, 21, 2500, rows=532) / 819e9)
    assert said and "at the HBM bandwidth" in said[0]
    assert 0 < read("mhc.decode_roofline") < 100
    assert 0 < read("mhc.round_mfu") < 100


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "xing4-l6", "rag-closed-c32")
    c = cell.config
    assert c["kind"] == "xing4_0"
    assert c["reduced"].keys() == {"num_hidden_layers",
                                   "first_k_dense_replace"}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (6, 1)
    assert (c["hc_mult"], c["hc_sinkhorn_iters"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["vocab_size"]) == (4, 20, 64, 4,
                                                           131072)
    assert {"assumed", "departures", "stands_for",
            "kv_bytes_per_token"} <= set(c)
    assert "9.59 GB" in c["reduced"]["num_hidden_layers"]["why"]
    assert "7680 B" in c["kv_bytes_per_token"]
    assert {"hyper-connections wrap each sublayer", "streams in and out",
            "clamp and epsilon", "precision", "rope_scaling", "hidden_act",
            "torch_dtype", "weights", "hyper-connection weights"} <= set(
                c["assumed"])
    assert len(c["departures"]) == 3
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 16384,
                                      "page_size": 16, "pool_tokens": 327680}
    assert cell.traffic["reference_prompt_lens"] == [24, 2000]
    names = {m["name"] for m in cell.per_layer}
    assert {"mhc.decode_roofline", "mhc.round_mfu",
            "mhc.stream_bytes_per_row", "moe.experts_hit_per_step",
            "moe.expert_load_max_over_mean", "kv.bytes_per_token",
            "step.mixed_round_ms"} <= names
    assert not {"gdn.decode_roofline", "ssm.decode_roofline",
                "scmoe.decode_roofline", "step.decode_weight_roofline",
                "step.decode_ms"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "xing4_0")
    from tpulab.models.spec import xing4_spec
    sp = xing4_spec(c)
    assert (sp.n_layers, sp.n_experts, sp.top_k, sp.hc_mult,
            sp.layer_kinds.count("moe")) == (6, 64, 4, 4, 5)
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"])
        assert c["source"] == row["source_url"]


def test_step_decode_ms_lists_the_seven_cells_it_had():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "step.decode_ms")
    assert metric["workloads"] == [w["name"] for w in bench["workloads"][:7]]
    assert CELL not in metric["workloads"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers",
                                               "first_k_dense_replace"]


def test_the_mix_fits_the_pool_and_no_operation_can_fail():
    """rag: prompts 698-6012, outputs 268-977: the pool holds the whole set
    at once (no preemption) and max_len the longest pair."""
    from harness.sizes import size_pairs
    traffic = spec.load_cell(CELL).traffic
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (698, 6012)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (268, 977)
    eng = traffic["engine"]
    assert pairs.sum() <= eng["pool_tokens"]
    assert pairs.sum(1).max() <= eng["max_len"]


def test_adapter_folds_the_softmax_factor_and_seeds_the_maps():
    """``wq_b`` is the published draw times 2.0047 (in bf16), the published
    one kept for the reference; a hyper-connection is the program's seeded
    one, not normal 0.02; everything else is."""
    adapter = spec.load_module("models", "xing4_0")
    tiny = spec.load_json(os.path.join(
        spec.PERF_DIR, "tests", "cells", "configs", "tiny-xing4.json"))
    from functools import partial

    from tpulab.models.spec import init_params, mla_scales, xing4_spec
    sp = xing4_spec(tiny)
    tree = jax.eval_shape(partial(init_params, sp, 256, 96))
    params, published = adapter.make_weights(tree, sp, tiny, 2**31 + 5)
    assert len(published) == 3
    q_scale = mla_scales(tiny)[0]
    assert q_scale == pytest.approx(2.0047, abs=1e-4)
    np.testing.assert_allclose(
        np.asarray(params["layer2"]["wq_b"], np.float32),
        q_scale * np.asarray(published[2], np.float32), rtol=2 ** -7)
    w1 = np.asarray(params["layer0"]["w1"], np.float32)
    assert 0.017 < w1.std() < 0.023
    assert (np.asarray(params["layer1"]["ln1"]["scale"], np.float32)
            == 1).all()
    hc = params["layer1"]["hc_ffn"]
    assert hc["phi"].dtype == jnp.bfloat16
    phi = np.asarray(hc["phi"], np.float32)
    assert 0.7 * 256 ** -0.5 < phi.std() < 1.3 * 256 ** -0.5
    assert (np.asarray(hc["alpha"], np.float32) == 1).all()
    bias = np.asarray(hc["bias"], np.float32)[8:].reshape(4, 4)
    assert np.diag(bias).mean() > bias[~np.eye(4, dtype=bool)].mean() + 0.3
    other = np.asarray(params["layer2"]["hc_attn"]["phi"], np.float32)
    assert np.abs(other - phi).max() > 0.01         # a draw a sublayer


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_xing4_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-xing4.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    got = line["metrics"]
    assert got["kv.bytes_per_token"]["value"] == 3 * 128 * 2
    assert got["mhc.stream_bytes_per_row"]["value"] == 4 * 64 * 2
    assert got["compiles_in_window.lm"]["value"] == 0
    assert 0 < got["moe.experts_hit_per_step"]["value"] <= 8
    assert "rehearsal" in line
