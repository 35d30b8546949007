"""Kind ``nemotron_h``: the reference against hand-unrolled cases, the
rooflines' counts against the issue's reckoning, the new readers on canned
contexts, the new cell's files, and a tiny overlay cell (a share of the
experts, layers of one sublayer) through ``perf/run.py`` end to end on the
CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "nemotron_h")
ROOFLINE = spec.load_module("rooflines", "nemotron_h")
NEMO = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                   "nemotron3-nano-ep8.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-nemotron.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron3-nano-ep8.rag"


# ------------------------------------------------------- the reference ----

def test_ssm_scan_against_two_tokens_unrolled_by_hand():
    """Two tokens, one head, in float64 numpy with every step written out:
    token 0 writes ``dt x (x) B`` into an empty state, token 1 decays it by
    ``exp(dt A)``, adds its own and reads it with ``C``, plus ``D x``."""
    rng = np.random.default_rng(3)
    x, b, c = (rng.standard_normal((2, 1, n)) for n in (3, 4, 4))
    dt, a, d = rng.uniform(0.1, 1, (2, 1)), -1.7, 0.6
    s0 = dt[0, 0] * np.outer(x[0, 0], b[0, 0])
    y0 = s0 @ c[0, 0] + d * x[0, 0]
    s1 = np.exp(dt[1, 0] * a) * s0 + dt[1, 0] * np.outer(x[1, 0], b[1, 0])
    f32 = lambda v: jnp.asarray(v, jnp.float32)              # noqa: E731
    got, state = REF.ssm_scan(f32(x), f32(dt), f32([a]), f32(b), f32(c),
                              f32([d]))
    np.testing.assert_allclose(np.asarray(got)[:, 0],
                               [y0, s1 @ c[1, 0] + d * x[1, 0]], rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(state)[0], s1, rtol=2e-5,
                               atol=2e-6)


def test_mamba2_mixer_gates_before_the_group_norm_and_convolves_xbc():
    """One head a group, width 2: the convolution runs over ``[x | B | C]``
    together with its bias, the gate multiplies BEFORE the norm, the norm is
    over a GROUP's channels."""
    rng = np.random.default_rng(4)
    heads, p, groups, n, d = 2, 2, 2, 3, 5
    din, gn = heads * p, groups * n
    w = lambda *s: rng.standard_normal(s) * 0.5              # noqa: E731
    leaves = {"in_proj": w(d, 2 * din + 2 * gn + heads),
              "conv_w": w(4, din + 2 * gn), "conv_b": w(din + 2 * gn),
              "dt_bias": w(heads), "a_log": w(heads), "d": w(heads),
              "norm": {"scale": 1 + w(din)}, "out_proj": w(din, d)}
    h = w(3, d)
    zxd = h @ leaves["in_proj"]
    z, xbc, dt = zxd[:, :din], zxd[:, din:2 * din + 2 * gn], zxd[:, -heads:]
    pad = np.concatenate([np.zeros((3, xbc.shape[1])), xbc])
    conv = sum(leaves["conv_w"][j] * pad[j:j + 3] for j in range(4))
    conv = conv + leaves["conv_b"]
    xbc = conv / (1 + np.exp(-conv))
    dt = np.log1p(np.exp(dt + leaves["dt_bias"]))
    a = -np.exp(leaves["a_log"])
    y = np.zeros((3, heads, p))
    for j in range(heads):                   # a head uses its own group here
        s = np.zeros((p, n))
        for t in range(3):
            xt = xbc[t, j * p:(j + 1) * p]
            bt = xbc[t, din + j * n:din + (j + 1) * n]
            ct = xbc[t, din + gn + j * n:din + gn + (j + 1) * n]
            s = np.exp(dt[t, j] * a[j]) * s + dt[t, j] * np.outer(xt, bt)
            y[t, j] = s @ ct + leaves["d"][j] * xt
    gated = (y.reshape(3, din) * (z / (1 + np.exp(-z)))).reshape(3, groups,
                                                                 -1)
    normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    want = (normed.reshape(3, din) * leaves["norm"]["scale"]) @ leaves[
        "out_proj"]
    got, state = REF.mamba2_mixer(
        jnp.asarray(h, jnp.float32),
        jax.tree_util.tree_map(lambda v: jnp.asarray(v, jnp.float32),
                               leaves),
        eps=1e-5, heads=heads, head_dim=p, groups=groups, state=n)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(state)[heads - 1], s, rtol=3e-5,
                               atol=3e-6)


def _tiny_layer(rng, d=8, e=6, f=4):
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.5, jnp.float32)
    return {"ln2": {"scale": 1 + w(d)},
            "moe": {"router": w(d, e), "bias": w(e) * 0.3, "w1": w(e, d, f),
                    "w2": w(e, f, d)},
            "shared": {"w1": w(d, 2 * f), "w2": w(2 * f, d)}}


def test_moe_block_by_hand_and_the_shares_add_up():
    """Top-2 of 6 by hand in float64: chosen by ``s + bias``, weighted by
    ``s`` over the chosen sum times 2.5, experts ``relu(.)^2``; three shares
    of two experts, with the shared expert counted once, are the uncut
    block; dropping the bias changes a choice."""
    rng = np.random.default_rng(5)
    p = _tiny_layer(rng)
    x = rng.standard_normal((7, 8)).astype(np.float32)
    f64 = lambda a: np.asarray(a, np.float64)                # noqa: E731
    h = f64(x) / np.sqrt((f64(x) ** 2).mean(-1, keepdims=True) + 1e-5) \
        * f64(p["ln2"]["scale"])
    s = 1 / (1 + np.exp(-(h @ f64(p["moe"]["router"]))))
    want, moved = np.zeros((7, 8)), 0
    for t in range(7):
        top = np.argsort(-(s[t] + f64(p["moe"]["bias"])), kind="stable")[:2]
        moved += set(top) != set(np.argsort(-s[t], kind="stable")[:2])
        for e in top:
            up = np.maximum(h[t] @ f64(p["moe"]["w1"][e]), 0) ** 2
            want[t] += 2.5 * s[t, e] / s[t, top].sum() * (
                up @ f64(p["moe"]["w2"][e]))
    assert moved >= 1
    sh = p["shared"]
    shared = np.maximum(h @ f64(sh["w1"]), 0) ** 2 @ f64(sh["w2"])
    kw = dict(eps=1e-5, top_k=2, scale=2.5, norm=True)
    counts = []
    got = np.asarray(REF.moe(jnp.asarray(x), p, first=0, counts=counts, **kw))
    np.testing.assert_allclose(got, want + shared, rtol=2e-5, atol=2e-6)
    assert counts[0].sum() == 14 and counts[0].shape == (6,)
    parts = []
    for first in (0, 2, 4):
        held = dict(p, moe=dict(p["moe"], w1=p["moe"]["w1"][first:first + 2],
                                w2=p["moe"]["w2"][first:first + 2]))
        parts.append(np.asarray(REF.moe(jnp.asarray(x), held, first=first,
                                        shared=False, **kw)))
    np.testing.assert_allclose(sum(parts) + shared, want + shared, rtol=2e-5,
                               atol=2e-6)
    assert all(np.abs(part).max() > 1e-3 for part in parts)


def test_hyper_of_reads_the_published_keys_and_the_share():
    hyper = REF.hyper_of(NEMO)
    assert len(hyper["pattern"]) == 52
    assert [hyper["pattern"].count(c) for c in "ME*"] == [23, 23, 6]
    assert "M*" in hyper["pattern"]      # no (mixer, FFN) pairing exists
    assert (hyper["m_heads"], hyper["m_head_dim"], hyper["groups"],
            hyper["state"]) == (64, 64, 8, 128)
    assert (hyper["n_heads"], hyper["n_kv_heads"], hyper["head_dim"]) == (
        32, 2, 128)
    assert (hyper["top_k"], hyper["scale"], hyper["norm"], hyper["first"],
            hyper["eps"]) == (6, 2.5, True, 0, 1e-5)
    assert REF.REFERENCE_STEPS == 32 and REF.REFERENCE_STREAMS == 4
    assert 0 < REF.STATE_TOLERANCE < REF.TOLERANCE < 1
    assert 0 < REF.ROUTE_TOLERANCE < REF.ROUTE_TOLERANCE_SHORT < 1
    assert REF.route_tolerance(24) == REF.ROUTE_TOLERANCE_SHORT
    assert REF.route_tolerance(2000) == REF.ROUTE_TOLERANCE


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.PERF_DIR, "reference", "nemotron_h.py")) as f:
        assert "import tpulab" not in f.read().split('"""', 2)[2]


def test_store_errors_by_hand():
    """``state_err`` is the difference's norm over the reference's;
    ``route_err`` half the L1 distance of the FIRST expert layer's
    histograms over its assignments."""
    rng = np.random.default_rng(6)
    want = {"state": rng.standard_normal((3, 2, 4, 5)),
            "routes": np.array([[10, 0, 2], [4, 4, 4]])}
    state = want["state"][0] * 1.01
    got = REF.store_errors(state, np.array([[9, 1, 2], [4, 4, 4]]), want)
    assert got["state_err"] == pytest.approx(0.01)
    # every head is 1 % off: the quartile and the least over heads too
    assert got["state_err_low"] == pytest.approx(0.01)
    assert got["state_err_min"] == pytest.approx(0.01)
    one = want["state"][0].copy()
    one[1] *= 1.05                      # one head of two off: the least is 0
    heads = REF.store_errors(one, want["routes"], want)
    assert heads["state_err_min"] == 0 and heads["state_err_low"] == (
        pytest.approx(0.0125))
    assert got["route_err"] == pytest.approx(1 / 12)
    assert got["route_err_all"] == pytest.approx(1 / 24)
    with pytest.raises(ValueError, match="served stores"):
        REF.store_errors(state[:1], want["routes"], want)


# -------------------------------------------------------- the rooflines ----

def test_parameter_and_state_counts_are_the_issues():
    """ISSUE 60's own table: a Mamba-2 layer 38.75 M, an attention layer
    23.40 M, a routed expert 9.98 M, shared expert and router 20.30 M,
    10.52 GB held at the published widths."""
    assert ROOFLINE.mamba_params(NEMO) == (
        2688 * 10304 + 6144 * 5 + 4096 * 2688) == 38_737_920
    assert ROOFLINE.attention_params(NEMO) == (
        2 * 2688 * 4096 + 2 * 2688 * 256) == 23_396_352
    assert ROOFLINE.expert_params(NEMO) == 2 * 2688 * 1856 == 9_977_856
    assert ROOFLINE.ffn_shared_params(NEMO) == (
        2688 * 128 + 2 * 2688 * 3712) == 20_299_776
    assert [ROOFLINE.pattern(NEMO).count(c) for c in "ME*"] == [23, 23, 6]
    outside = 23 * 38_737_920 + 6 * 23_396_352 + 23 * 20_299_776
    assert ROOFLINE.outside_expert_params(NEMO) == outside == 1_498_245_120
    assert ROOFLINE.model_params(NEMO) == (
        outside + 23 * 16 * 9_977_856 + 2 * 16384 * 2688)
    assert 10.51e9 < 2 * ROOFLINE.model_params(NEMO) < 10.53e9
    assert ROOFLINE.state_bytes_per_lane(NEMO) == 23 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2) == 49_082_368
    assert ROOFLINE.kv_bytes_per_token(NEMO) == 6144
    # a cut in depth keeps the pattern's first letters
    cut = dict(NEMO, num_hidden_layers=43)
    assert [ROOFLINE.pattern(cut).count(c) for c in "ME*"] == [19, 18, 6]


def test_step_bytes_and_round_flops_by_hand():
    """ISSUE 60's decode step: 32 lanes at 2 k of context with 12.6 of 16
    experts hit an expert layer: ~12.3 GB, of which the state is 3.1."""
    got = ROOFLINE.decode_step_bytes(NEMO, 32, 12.6, 2048)
    assert got == (2 * (1_498_245_120 + 23 * 12.6 * 9_977_856 + 16384 * 2688)
                   + 32 * (2 * 49_082_368 + 2048 * 6144))
    assert 12.0e9 < got < 12.6e9
    assert ROOFLINE.decode_step_bytes(NEMO, 0, 0, 0) == 2 * (
        1_498_245_120 + 16384 * 2688)
    whole = ROOFLINE.round_bytes(NEMO, 32, 2048)
    assert whole == (2 * (ROOFLINE.model_params(NEMO) - 16384 * 2688)
                     + 32 * (2 * 49_082_368 + 2048 * 6144))
    assert whole > got
    assert ROOFLINE.ssm_row_flops(NEMO) == 4 * 64 * 64 * 128
    assert ROOFLINE.attention_pair_flops(NEMO) == 4 * 32 * 128
    flops = ROOFLINE.round_flops(NEMO, 540, 540 * 6 * 23 / 8, 1e6, 30)
    assert flops == (2.0 * 540 * 1_498_245_120 + 540 * 23 * 4 * 64 * 64 * 128
                     + 2.0 * 540 * 6 * 23 / 8 * 9_977_856
                     + 1e6 * 6 * 4 * 32 * 128 + 2.0 * 30 * 16384 * 2688)
    assert 1.7e12 < flops < 2.0e12           # the issue's ~1.9 TF a round
    cost = ROOFLINE.ssd_chunk_cost(512, NEMO)
    per_chunk = 2 * (8 * 128 * 128 * 128
                     + 64 * (128 * 128 * 64 + 2 * 128 * 64 * 128))
    assert cost["flops"] == 4 * per_chunk
    assert ROOFLINE.ssd_chunk_cost(129, NEMO)["flops"] == 2 * per_chunk
    assert (ROOFLINE.ssd_chunk_cost(512, NEMO, segments=3)["bytes"]
            - cost["bytes"]) == 4 * 2 * 2 * 64 * 64 * 128


# -------------------------------------------------------- the readers ----

class _Cell:
    config = NEMO

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None, kind="mamba2"):
    def moe(scale):
        rows = [[scale * (1 + (e % 3)) for e in range(128)]
                for _ in range(23)]
        return {"expert_layers": list(range(23)), "assignments": rows,
                "first": 0, "held": 16, "zero_columns": 0,
                "assignments_here": [sum(r[:16]) for r in rows],
                "decode_steps": 10 * scale, "experts_hit": 23 * 126 * scale}
    state = {"kind": kind, "lanes": 32, "bytes_per_lane": 49_082_368}
    pool = {"n_pages": 20481, "page_size": 16, "hbm_bytes": 20481 * 16 * 6144}

    def ssd(scale):
        return {"chunk": 128,
                "decode": {"chunks": 0, "passes": 0, "rows": 0,
                           "one_token_rows": 23 * 3100 * scale},
                "round": {"chunks": 23 * 160 * scale,
                          "passes": 23 * 200 * scale,
                          "rows": 23 * 18000 * scale,
                          "one_token_rows": 23 * 1000 * scale}}

    def dispatch(scale):
        return {"decode_block_steps": 100 * scale, "mixed_tokens": 19000
                * scale, "round_attn_pairs": 4_000_000 * scale, "kinds": {
                    "decode": 50 * scale, "mixed": 40 * scale, "verify": 0},
                "lane_work": {
                    "decode": {"passes": 3100 * scale, "rows": 3100 * scale,
                               "keys": 3100 * 2500 * scale},
                    "round": {"passes": 1040 * scale, "rows": 19000 * scale,
                              "keys": 1040 * 2000 * scale}}}
    return {"cell": _Cell, "trace": trace, "gauges": [],
            "counters_before": {"moe": moe(1), "state": state, "pool": pool,
                                "dispatch": dispatch(1), "ssd": ssd(1)},
            "counters_after": {"moe": moe(3), "state": state, "pool": pool,
                               "dispatch": dispatch(3), "ssd": ssd(3)}}


def test_new_readers_on_a_canned_context(monkeypatch):
    read = lambda name, ctx: spec.load_module(               # noqa: E731
        "layer_metrics", name).read(ctx)
    ctx = _ctx()
    assert read("ssm.state_bytes_per_lane", ctx) == 49_082_368
    assert read("kv.bytes_per_token", ctx) == 6144
    assert read("moe.experts_hit_per_step", ctx) == pytest.approx(12.6)
    # 18,000 rows a state layer in 160 chunks of 128
    assert read("ssd.chunk_fill", ctx) == pytest.approx(
        100 * 18000 / (160 * 128))
    for name in ("ssd.decode_roofline", "ssd.round_mfu"):
        assert read(name, ctx) is None                          # no trace

    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.040, 0.044]},
        "jit_paged_decode_block_k1": {"durations_s": [0.021]},
        "jit_paged_mixed_step": {"durations_s": [0.030, 0.034]}}}
    ctx = _ctx(trace)
    step = (0.040 + 0.044 + 0.021) / 5
    assert read("ssd.decode_roofline", ctx) == pytest.approx(
        100 * ROOFLINE.decode_step_bytes(NEMO, 31, 12.6, 2500) / 819e9 / step)
    # a round of 475 tokens, 26 lanes with a segment at 2,000 keys; of all
    # the rows routed (19,000 + 3,100) the rounds' share of the held
    # assignments
    here = 2 * 23 * sum(1 + e % 3 for e in range(16))
    work = (475, here * 19000 / 22100 / 80, 100_000, 26)
    assert read("ssd.round_mfu", ctx) == pytest.approx(
        100 * ROOFLINE.round_flops(NEMO, *work) / 197e12 / 0.032)
    floors = spec.load_module("layer_metrics", "ssd.round_mfu").bounds(ctx)
    assert floors["bytes_s"] == pytest.approx(
        ROOFLINE.round_bytes(NEMO, 26, 2000) / 819e9)
    assert 0 < read("ssd.decode_roofline", ctx) < 100
    assert 0 < read("ssd.round_mfu", ctx) < 100
    # a program without the counters (the parent), or a model with another
    # kind of state: nothing to read, no error
    other = _ctx(trace, kind="gdn")
    for name in ("ssd.decode_roofline", "ssd.round_mfu"):
        assert read(name, other) is None
    bare = {"cell": _Cell, "trace": trace, "gauges": [],
            "counters_before": {"dispatch": {}},
            "counters_after": {"dispatch": {}}}
    for name in ("ssd.decode_roofline", "ssd.round_mfu", "ssd.chunk_fill"):
        assert read(name, bare) is None


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "nemotron3-nano-ep8", "rag-closed-c32")
    c = cell.config
    assert c["kind"] == "nemotron_h"
    assert c["reduced"].keys() == {"n_routed_experts", "vocab_size"}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (52, 16, 16384)
    assert c["share"]["n_routed_experts"] == 128 and c["share"]["chips"] == 8
    assert c["share"]["first_expert"] == 0 and c["num_experts_per_tok"] == 6
    assert c["share"]["vocab_size"] == 131072 == 8 * c["vocab_size"]
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["conv_kernel"],
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["routed_scaling_factor"]) == (2688, 64, 64, 8, 128, 4, 1856,
                                            3712, 2.5)
    assert {"assumed", "departures", "stands_for", "state_bytes_per_lane",
            "kv_bytes_per_token"} <= set(c)
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 16384,
                                      "page_size": 16, "pool_tokens": 327680}
    assert cell.traffic["reference_prompt_lens"] == [24, 2000]
    names = {m["name"] for m in cell.per_layer}
    assert {"ssd.decode_roofline", "ssd.round_mfu", "ssd.chunk_fill",
            "moe.assignments_here_skew", "moe.experts_hit_per_step",
            "moe.expert_load_max_over_mean", "kv.bytes_per_token",
            "ssm.state_bytes_per_lane", "sched.block_k_mean",
            "sched.ahead_share"} <= names
    assert not {"step.decode_ms", "gdn.decode_roofline",
                "ssm.decode_roofline"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "nemotron_h")
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"])
        assert c["source"] == row["source_url"]
        assert {k: c["share"][k] for k in ("n_routed_experts",
                                           "vocab_size")} == {
            k: row["config"][k] for k in ("n_routed_experts", "vocab_size")}


def test_the_benchmark_gained_one_configuration_one_cell_three_metrics():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert bench["configs"][-1]["name"] == "nemotron3-nano-ep8"
    assert bench["configs"][-1]["reduced"] == ["n_routed_experts",
                                               "vocab_size"]
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "ssd.decode_roofline", "ssd.round_mfu", "ssd.chunk_fill"]
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"][-3:])
    for entry in (bench["configs"][-1], bench["workloads"][-1]):
        assert 0 < len(entry["why"]) <= 200
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 11 and len(bench["configs"]) == 11
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # the two that read null where every dispatch is a round list their cells
    for name in ("sched.block_k_mean", "sched.ahead_share"):
        assert by_name[name]["workloads"] == cells
    for name in ("moe.expert_load_max_over_mean", "moe.experts_hit_per_step",
                 "moe.assignments_here_skew", "kv.bytes_per_token",
                 "ssm.state_bytes_per_lane"):
        assert by_name[name]["workloads"][-1] == CELL
    assert CELL not in by_name["step.decode_ms"]["workloads"]


def test_adapter_fills_the_leaves_by_the_stated_rule():
    adapter = spec.load_module("models", "nemotron_h")
    from tpulab.models.spec import nemotron_h_spec
    tiny = spec.load_json(os.path.join(spec.PERF_DIR, "tests", "cells",
                                       "configs", "tiny-nemotron.json"))
    sp = adapter.spec_of(tiny)
    assert (sp.n_experts, sp.experts_held, sp.expert_first) == (16, 4, 4)
    assert sp == nemotron_h_spec(dict(tiny, n_routed_experts=16), first=4,
                                 held=4)
    key = jax.random.key(0)
    fill = lambda path, *shape: np.asarray(adapter.fill_rule(   # noqa: E731
        path, shape, key, sp))
    a = np.exp(fill("['layer0']['mamba2']['a_log']", 4096))
    assert 1.0 <= a.min() < 1.1 and 15.9 < a.max() <= 16.0
    dt = np.log1p(np.exp(fill("['layer0']['mamba2']['dt_bias']", 4096)))
    assert 0.99e-3 < dt.min() < 1.1e-3 and 0.09 < dt.max() < 0.1001
    assert (fill("['layer0']['mamba2']['d']", 8) == 1).all()
    assert (fill("['layer0']['mamba2']['norm']['scale']", 8) == 1).all()
    for leaf in ("conv_w", "conv_b"):
        w = fill(f"['layer0']['mamba2']['{leaf}']", 4, 1024)
        assert 0.49 < np.abs(w).max() <= 0.5
    # the served experts' padding is zero, the published columns are not
    w1 = fill("['layer1']['moe']['w1']", 2, 8, sp.moe_ff_served)
    w2 = fill("['layer1']['moe']['w2']", 2, sp.moe_ff_served, 8)
    assert not w1[:, :, sp.moe_ff:].any() and not w2[:, sp.moe_ff:].any()
    assert np.abs(w1[:, :, :sp.moe_ff]).min() > 0
    assert np.abs(w2[:, :sp.moe_ff]).min() > 0
    assert 0.015 < fill("['layer1']['shared']['w1']", 64, 64).std() < 0.025


def test_the_mix_is_the_issues_and_no_operation_can_fail():
    from harness.sizes import size_pairs
    traffic = spec.load_json(os.path.join(spec.PERF_DIR, "traffic",
                                          "rag-closed-c32.json"))
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (698, 6012)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (268, 977)
    eng = traffic["engine"]
    assert pairs.sum(1).max() <= eng["pool_tokens"] // eng["lanes"]
    assert pairs.sum(1).max() <= eng["max_len"]


def test_tiny_nemotron_cell_end_to_end_on_the_cpu():
    """The tiny cell holds experts 4 .. 8 of 16: the served path and the
    reference leave the same twelve out, on layers of one sublayer."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-nemotron.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    m = out["metrics"]
    assert "state_kind=mamba2" in proc.stdout
    assert "experts=4..+4 of 16" in proc.stdout
    assert "served_expert_width=128" in proc.stdout
    # 3 Mamba-2 layers x (4 x 8 x 16 float32 + 3 x 96 bf16); 2 attention
    # layers of 2 KV heads x 16 in bf16
    assert m["ssm.state_bytes_per_lane"]["value"] == 3 * (2048 + 576)
    assert m["kv.bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 2
    assert m["moe.assignments_here_skew"]["value"] < 15
    assert 0 < m["moe.experts_hit_per_step"]["value"] <= 4
    assert 0 < m["ssd.chunk_fill"]["value"] <= 100
    assert m["sched.block_k_mean"]["value"] >= 1
    assert "ssd.decode_roofline" not in m     # no TPU trace on a CPU
    assert "ssd.round_mfu" not in m


@pytest.mark.parametrize("name, limit, at", [
    ("state_err_low", "STATE_TOLERANCE", 0),
    ("state_err_low", "STATE_TOLERANCE", 1), ("route_err", "ROUTE_TOLERANCE", 1)],
    ids=["state-short", "state-long", "route-long"])
def test_each_stores_limit_lies_between_its_two_readings(name, limit, at):
    """bf16 serving's largest reading under the limit, the store kept one
    precision lower over it, with room on both sides (the readings are the
    reference file's own record of my chip runs, PR 60; ``at``: after the
    short prompts or the long).  The short prompts' routing guards a gross
    fault alone: its limit lies over both readings."""
    fault = "bf16_state" if name == "state_err_low" else "bf16_router"
    read = lambda text: [float(v) for v in                 # noqa: E731
                         text.split(" / ")[at].split("-")]
    bf16 = max(read(REF.STORE_READINGS[name]["bf16"]))
    low = min(read(REF.STORE_READINGS[name][fault]))
    assert 1.2 * bf16 < getattr(REF, limit) < low / 1.2
    assert REF.ROUTE_TOLERANCE_SHORT > max(
        float(v) for v in REF.STORE_READINGS["route_err"]["bf16_router"]
        .split(" / "))
    logits = [float(v) for part in REF.TOLERANCE_READINGS["bf16"].split(" / ")
              for v in part.split("-")]
    assert 1.3 * max(logits) < REF.TOLERANCE < min(
        float(v) for v in REF.TOLERANCE_READINGS["bf16_router"].split(" / "))
