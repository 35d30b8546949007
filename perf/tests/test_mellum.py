"""Kind ``mellum``: the reference against cases written out by hand, the
rooflines' counts against the issue's reckoning, the new readers on canned
contexts, the new cell's files, and a tiny overlay cell through
``perf/run.py`` end to end on the CPU."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "mellum")
ROOFLINE = spec.load_module("rooflines", "mellum")
MELLUM = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                     "mellum2-l8.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-mellum.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "mellum2-l8.mixedlen"
#: the readers this kind brings (``swa.decode_roofline`` comes with a cell
#: whose traced tail holds a decode block: ROADMAP W18)
NEW = ("swa.window_keys_share", "swa.cache_bytes_per_position",
       "swa.round_mfu")
f64 = lambda a: np.asarray(a, np.float64)      # noqa: E731


# ------------------------------------------------------- the reference ----

def _layer(rng, hidden=12, hq=4, g=2, d=4):
    w = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return {"wqkv": w(hidden, (hq + 2 * g) * d), "wo": w(hq * d, hidden),
            "q_norm": {"scale": 1 + 0.1 * w(d)},
            "k_norm": {"scale": 1 + 0.1 * w(d)}}


@pytest.mark.parametrize("window", [0, 3], ids=["full", "window-3"])
def test_attention_by_hand_in_float64(window):
    """Seven tokens through one layer: the per-head norms, rotate-half RoPE
    with a factor on cos and sin, GQA, and the mask's edge (key ``i - 3``
    unseen, ``i - 2`` seen) written out."""
    rng = np.random.default_rng(1)
    hq, g, d, t, factor = 4, 2, 4, 7, 1.25
    p = _layer(rng)
    x = rng.standard_normal((t, 12)).astype(np.float32)
    ln = 1 + 0.1 * rng.standard_normal(12).astype(np.float32)
    inv = np.asarray([1.0, 0.1], np.float32)
    out, k_ref, v_ref = REF.attention(
        jnp.asarray(x), jnp.asarray(ln), p, inv, n_heads=hq, n_kv_heads=g,
        eps=1e-6, factor=factor, window=window, block=4)
    norm = lambda z, s: z / np.sqrt((z ** 2).mean(-1, keepdims=True) + 1e-6
                                    ) * f64(s)
    h = norm(f64(x), ln)
    qkv = h @ f64(p["wqkv"])
    q = norm(qkv[:, :hq * d].reshape(t, hq, d), p["q_norm"]["scale"])
    k = norm(qkv[:, hq * d:(hq + g) * d].reshape(t, g, d),
             p["k_norm"]["scale"])
    v = qkv[:, (hq + g) * d:].reshape(t, g, d)

    def rope(z):
        ang = np.arange(t)[:, None] * f64(inv)[None, :]
        cos, sin = np.cos(ang)[:, None] * factor, np.sin(ang)[:, None] * factor
        a, b = z[..., :2], z[..., 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    q, k = rope(q), rope(k)
    got = np.zeros((t, hq, d))
    for i in range(t):
        lo = max(i - window + 1, 0) if window else 0
        for head in range(hq):
            kk, vv = k[lo:i + 1, head // 2], v[lo:i + 1, head // 2]
            s = kk @ q[i, head] / np.sqrt(d)
            w = np.exp(s - s.max())
            got[i, head] = (w / w.sum()) @ vv
    want = f64(x) + got.reshape(t, -1) @ f64(p["wo"])
    np.testing.assert_allclose(f64(out), want, atol=2e-5)
    np.testing.assert_allclose(f64(k_ref), k.reshape(t, -1), atol=2e-5)
    np.testing.assert_allclose(f64(v_ref), v.reshape(t, -1), atol=2e-5)


def test_yarn_table_by_hand_and_the_published_factor():
    inv = REF.yarn_inv_freq(128, 500000.0, 16.0, 8192.0, 32.0, 1.0)
    plain = REF.plain_inv_freq(128, 500000.0)
    corr = lambda t: 128 * np.log(8192 / (2 * np.pi * t)) / (
        2 * np.log(500000.0))
    assert (int(np.floor(corr(32))), int(np.ceil(corr(1)))) == (18, 35)
    for j in range(64):
        ramp = min(max((j - 18) / (35 - 18), 0.0), 1.0)
        want = plain[j] * ((1 - ramp) + ramp / 16)
        assert abs(inv[j] - want) <= 1e-6 * want
    hy = REF.hyper_of(MELLUM)
    assert hy["full_factor"] == 1.2772588722239782
    assert abs(hy["full_factor"] - (0.1 * np.log(16) + 1)) < 1e-12
    np.testing.assert_allclose(hy["full_inv"], inv, rtol=1e-6)
    np.testing.assert_allclose(hy["window_inv"], plain, rtol=1e-6)
    assert (hy["window"], hy["top_k"], hy["n_heads"], hy["n_kv_heads"]) == (
        1024, 8, 32, 4)
    assert hy["layer_types"][:8] == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)


def test_experts_take_the_top_k_renormalised():
    rng = np.random.default_rng(2)
    hidden, e, f, t = 8, 6, 5, 9
    w = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    p = {"ln2": {"scale": 1 + 0.1 * w(hidden)},
         "moe": {"router": w(hidden, e), "w13": w(e, hidden, 2 * f),
                 "w2": w(e, f, hidden)}}
    x = w(t, hidden)
    got = f64(REF.experts(x, p, eps=1e-6, top_k=2))
    xs = f64(x)
    h = xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-6) * f64(
        p["ln2"]["scale"])
    z = h @ f64(p["moe"]["router"])
    prob = np.exp(z - z.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want = xs.copy()
    for i in range(t):
        top = np.argsort(-prob[i], kind="stable")[:2]
        for ex in top:
            w13, w2 = f64(p["moe"]["w13"][ex]), f64(p["moe"]["w2"][ex])
            a, b = h[i] @ w13[:, :f], h[i] @ w13[:, f:]
            want[i] += prob[i, ex] / prob[i, top].sum() * (
                (a / (1 + np.exp(-a)) * b) @ w2)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(spec.PERF_DIR, "reference", "mellum.py"),
              encoding="utf-8") as f:
        text = f.read()
    assert "import tpulab" not in text and "from tpulab" not in text


def test_store_errors_read_each_group_where_it_holds_rows():
    """The window group's rows start at ``window_start``; layer 0, layer 1
    and the first full layer are the named numbers; a layer's rows in the
    other group's order read large."""
    rng = np.random.default_rng(3)
    kinds = ("sliding_attention",) * 3 + ("full_attention",)
    t, t0 = 40, 16
    want = rng.standard_normal((4, 2, t, 8))
    served = {"full": want[3:4].copy(), "window": want[:3, :, t0:].copy(),
              "window_start": t0}
    served["window"][1] *= 1.01          # layer 1: every row 1 % off
    served["full"][0, 0, :5] = 0         # five of 40 key rows of the full
    got = REF.store_errors(served, want, kinds)
    assert got["kv_err"] < 1e-12 and abs(got["kv1_err"] - 0.01) < 1e-9
    assert got["full_kv_err"] < 1e-12    # a median: five rows do not move it
    assert got["layer_kv_err"].shape == (4,)
    with pytest.raises(ValueError, match="served rows"):
        REF.store_errors(dict(served, window_start=8), want, kinds)
    both = REF.summary([dict(got, logprob_err=np.zeros(4),
                             argmax_gap=np.zeros(4))] * 2)
    assert abs(both["layers_kv_err"] - 0.01) < 1e-9
    assert REF.KV_TOLERANCE < REF.KV1_TOLERANCE < REF.FULL_KV_TOLERANCE
    assert REF.KV_TOLERANCE < REF.LAYERS_TOLERANCE_SHORT < (
        REF.LAYERS_TOLERANCE)


@pytest.mark.parametrize("length,bf16,fp8", [(24, 0.0125, 0.0319),
                                             (9000, 0.0177, 0.0316)])
def test_the_layers_limit_lies_between_its_two_readings(length, bf16, fp8):
    """``layers_kv_err`` alone reads layers 2 and 4 to 7, so fp8 pages in
    THOSE layers must fail it: a limit a length, with room on both sides of
    the readings on the chip (``TOLERANCE_READINGS``)."""
    limit = REF.layers_tolerance(length)
    assert 1.3 * bf16 < limit < fp8 / 1.3
    deep = next(v for k, v in REF.TOLERANCE_READINGS.items()
                if k.startswith("fp8_deep"))
    assert f"{bf16:.4f}" in REF.TOLERANCE_READINGS["bf16"]
    assert f"{fp8:.4f}" in deep


# ------------------------------------------------------- the rooflines ----

def test_parameter_and_cache_counts_are_the_issues():
    c = MELLUM
    assert ROOFLINE.layers_by_kind(c) == (2, 6)
    assert ROOFLINE.attention_params(c) == 21_233_664
    assert ROOFLINE.router_params(c) == 147_456
    assert 64 * ROOFLINE.expert_params(c) == 396_361_728
    assert ROOFLINE.head_params(c) == 98304 * 2304
    # 3,794.97 M with the 4,864 norm scales a layer and the final norm's
    assert ROOFLINE.model_params(c) + 8 * 4864 + 2304 == 3_794_968_832
    assert ROOFLINE.kv_bytes_per_token(c) == {"full": 4096, "window": 12288}
    assert c["kv_bytes_per_token"]["full"] == 4096
    assert c["kv_bytes_per_token"]["window"] == 12288


def test_step_bytes_and_round_flops_by_hand():
    c = MELLUM
    # the K/V rows 30 lanes read at 12,000 keys, 980 inside the window
    assert ROOFLINE.decode_kv_bytes(c, 30, 12000, 980) == 30 * (
        12000 * 4096 + 980 * 12288)
    assert ROOFLINE.decode_kv_bytes(c, 30, 12000, 12000) == 30 * 12000 * 16384
    # a round of 532 rows, 4,256 assignments a layer, 5 M pairs on a full
    # layer and 0.54 M inside the window, 3 head rows
    flops = ROOFLINE.round_flops(c, 532, 8 * 4256, 5e6, 0.54e6, 3)
    assert flops == (2 * 532 * 8 * 21_381_120 + 2 * 8 * 4256 * 6_193_152
                     + (2 * 5e6 + 6 * 0.54e6) * 4 * 4096
                     + 2 * 3 * 226_492_416)
    assert ROOFLINE.round_bytes(c, 3, 9000, 1500) == 2 * (
        ROOFLINE.model_params(c) - 226_492_416) + 3 * (
            9000 * 4096 + 1500 * 12288)


# --------------------------------------------------- the new readers ----

class _Cell:
    config = MELLUM

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None, window=True, gauges=()):
    def moe(scale):
        rows = [[scale * (1 + (e % 3)) for e in range(64)] for _ in range(8)]
        return {"expert_layers": list(range(8)), "assignments": rows,
                "zero_first": 64, "zero_columns": 0, "first": 0, "held": 64,
                "assignments_here": [sum(r) for r in rows],
                "decode_steps": 10 * scale, "experts_hit": 8 * 400 * scale}
    pool = {"n_pages": 32769, "page_size": 16, "page_nbytes": 65536}
    if window:
        pool["groups"] = {"full": {"page_nbytes": 65536, "layers": 2},
                          "window": {"page_nbytes": 196608, "layers": 6}}

    def dispatch(scale):
        extra = {"window_keys": 3000 * 1000 * scale} if window else {}
        extra_r = {"window_keys": 840 * 1500 * scale} if window else {}
        out = {"decode_block_steps": 100 * scale,
               "mixed_tokens": 40 * 532 * scale,
               "mixed_rows": 40 * 544 * scale,
               "kinds": {"decode": 50 * scale, "mixed": 40 * scale,
                         "verify": 0},
               "round_attn_pairs": 40 * 5_000_000 * scale,
               "lane_work": {
                   "decode": dict(passes=3000 * scale, rows=3000 * scale,
                                  keys=3000 * 12000 * scale, **extra),
                   "round": dict(passes=840 * scale, rows=40 * 532 * scale,
                                 keys=840 * 9000 * scale, **extra_r)}}
        if window:
            out["round_window_pairs"] = 40 * 540_000 * scale
        return out

    def counters(scale):
        return {"moe": moe(scale), "pool": pool, "dispatch": dispatch(scale)}
    return {"cell": _Cell, "trace": trace, "gauges": list(gauges),
            "say": None, "counters_before": counters(1),
            "counters_after": counters(3)}


TRACE = {"modules": {
    "jit_paged_decode_block_k2": {"durations_s": [0.034, 0.036]},
    "jit_paged_decode_block_k1": {"durations_s": [0.018]},
    "jit_paged_mixed_step": {"durations_s": [0.061, 0.063]}}}
GAUGES = [{"decode_pages": 20000, "decode_window_pages": 2400,
           "decode_positions": 320000},
          {"decode_pages": 0, "decode_window_pages": 0,
           "decode_positions": 0}]


def test_new_readers_on_a_canned_context(monkeypatch):
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx(TRACE, gauges=GAUGES)
    share = read("swa.window_keys_share", ctx)
    assert abs(share - 100 * (3000 * 1000 + 840 * 1500)
               / (3000 * 12000 + 840 * 9000)) < 1e-9
    per = read("swa.cache_bytes_per_position", ctx)
    assert per == (20000 * 65536 + 2400 * 196608) / 320000
    assert 4096 < per < 8192
    # a program without the counters (the parent under these files), and a
    # model without window layers: nothing, and no error
    old = _ctx(TRACE, window=False, gauges=[{"decode_pages": 1,
                                             "decode_positions": 16}])
    for name in NEW:
        assert read(name, old) is None
    assert read("swa.cache_bytes_per_position", _ctx(TRACE)) is None
    assert read("swa.round_mfu", _ctx(None, gauges=GAUGES)) is None
    # the shares, over a device the peaks' table knows
    import jax

    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    peaks = spec.load_json(os.path.join(spec.PERF_DIR, "peaks.json"))[
        "devices"]["TPU v5 lite"]
    mfu = read("swa.round_mfu", ctx)
    tokens, pairs = 532, 5_000_000
    rows_routed = 40 * 532 + 3000
    here = 2 * 8 * sum(1 + (e % 3) for e in range(64))
    expert_rows = here * (2 * 40 * 532) / (2 * rows_routed) / 80
    flops = ROOFLINE.round_flops(MELLUM, tokens, expert_rows, pairs, 540_000,
                                 21)
    assert abs(mfu - 100 * flops / peaks["bf16_flops_per_s"] / 0.062) < 1e-6
    assert 0 < mfu < 100


# ---------------------------------------------------- the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "mellum2-l8", "mixedlen-closed-c32")
    c = cell.config
    assert c["kind"] == "mellum"
    assert c["reduced"].keys() == {"num_hidden_layers"}
    assert (c["reduced"]["num_hidden_layers"]["from"],
            c["reduced"]["num_hidden_layers"]["to"]) == (28, 8)
    assert (c["num_hidden_layers"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["vocab_size"], c["sliding_window"], c["hidden_size"],
            c["head_dim"]) == (8, 64, 8, 896, 98304, 1024, 2304, 128)
    assert len(c["layer_types"]) == len(c["mlp_layer_types"]) == 28
    assert {"assumed", "departures", "stands_for", "layout",
            "kv_bytes_per_token"} <= set(c)
    assert c["layout"] == {"model": 1}
    why = c["reduced"]["num_hidden_layers"]["why"]
    assert "3,794.97 M" in why and "7.59 GB" in why
    assert {"qk_norm", "window_edge", "torch_dtype"} <= set(c["assumed"])
    assert "mtp_head" in c["departures"]
    assert "four pipeline stages" in c["stands_for"]
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 32768,
                                      "page_size": 16, "pool_tokens": 524288}
    assert cell.traffic["reference_prompt_lens"] == [24, 9000]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"moe.experts_hit_per_step",
                          "moe.expert_load_max_over_mean", "kv.run_block_share",
                       "kv.pages_in_use_peak", "kv.preemptions",
                       "step.mixed_round_ms"} <= names
    assert not {"gdn.decode_roofline", "cca.decode_roofline",
                "swa.decode_roofline", "step.decode_ms",
                "eva.cache_bytes_per_position", "kv.bytes_per_token",
                "step.decode_weight_roofline"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "mellum")
    from tpulab.models.spec import mellum_spec
    sp = mellum_spec(c)
    assert (sp.n_layers, sp.n_experts, sp.top_k, sp.window,
            sp.page_groups) == (8, 64, 8, 1024, (("full", 2), ("window", 6)))
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"])
        assert c["source"] == row["source_url"]


def test_the_benchmark_gained_one_configuration_one_cell_three_metrics():
    """By NAME, not by place: the next PR appends behind these entries.
    ``swa.decode_roofline`` is not brought and ``step.decode_ms`` does not
    list this cell: 99.6 % of its dispatches are mixed rounds (a prompt
    always waits), so a 3 s traced tail holds no decode block and neither
    would find anything to read there (as ``xing4-l6.rag`` is left off
    ``step.decode_ms``); a reader no run executes is not carried."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    named = lambda key, name: next(x for x in bench[key] if x["name"] == name)
    cell = named("workloads", CELL)
    assert cell == {"name": CELL, "config": "mellum2-l8",
                    "traffic": "mixedlen-closed-c32", "chips": 1,
                    "why": cell["why"]}
    assert named("configs", "mellum2-l8")["reduced"] == ["num_hidden_layers"]
    for name in NEW:
        m = named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert not [m for m in bench["per_layer"]
                if m["name"] == "swa.decode_roofline"]
    assert not os.path.exists(os.path.join(
        spec.PERF_DIR, "layer_metrics", "swa.decode_roofline.py"))
    assert CELL not in named("per_layer", "step.decode_ms")["workloads"]
    for name in ("moe.expert_load_max_over_mean", "moe.experts_hit_per_step"):
        assert named("per_layer", name)["workloads"][-1] == CELL
    assert all(len(x["why"]) <= 200
               for x in bench["configs"] + bench["workloads"])
    assert len(bench["workloads"]) == 10 and all(
        w["chips"] == 1 for w in bench["workloads"])


def test_the_mix_fits_the_full_group_and_no_operation_can_fail():
    """mixedlen-closed-c32: prompts 951-30720, outputs 268-977: the full
    group holds the whole set at once (no preemption: 379 k of 524,288
    tokens, 72 %) and max_len the longest pair."""
    from harness.sizes import size_pairs
    traffic = spec.load_cell(CELL).traffic
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (951, 30720)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (268, 977)
    assert (pairs[:, 0] <= 3970).sum() == 8 and (
        pairs[:, 0] >= 16906).sum() == 8 and (pairs[:, 0] == 30720).sum() == 3
    eng = traffic["engine"]
    assert 0.70 < pairs.sum() / eng["pool_tokens"] < 0.74
    assert pairs.sum(1).max() <= eng["max_len"]
    assert (traffic["prompt_len"], traffic["output_len"]) == (
        {"dist": "lognormal", "median": 8192, "sigma": 1.0, "min": 512,
         "max": 30720},
        {"dist": "lognormal", "median": 512, "sigma": 0.3, "min": 192,
         "max": 1536})
    assert (traffic["generator"], traffic["concurrency"], traffic["set_size"],
            traffic["pairing_seed"], traffic["channels"],
            traffic["ramp_max_s"]) == ("closed_replay", 32, 32, 1, 4, 120)


# ------------------------------------------------ the tiny overlay cell ----

def test_tiny_mellum_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-mellum.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    for name in ("kv_err", "kv1_err", "full_kv_err", "layers_kv_err"):
        assert name in proc.stdout
    # the 700-token streams left two key blocks of 256 rows behind them
    assert "held rows from position 512 on" in proc.stdout
    assert "page groups: full layers=1" in proc.stdout
    got = line["metrics"]
    assert got["compiles_in_window.lm"]["value"] == 0
    assert got["kv.preemptions"]["value"] == 0
    assert 0 < got["swa.window_keys_share"]["value"] < 100
    # a full layer's 512 B a position and the window blocks' share
    assert 2 * 2 * 32 * 2 < got["swa.cache_bytes_per_position"]["value"]
    assert 0 < got["moe.experts_hit_per_step"]["value"] <= 8
    assert got["kv.run_block_share"]["value"] > 0
    assert "rehearsal" in line


class _Direct:
    """``check_reference``'s client without the RPC: a call's greedy
    streams on the engine itself, all submitted at once (the engine's lanes
    are the callers), a prompt the call does not carry made as the client
    makes it."""

    def __init__(self, engine):
        self.engine = engine

    def call(self, request):
        from harness.sizes import prompt_tokens
        futures = [self.engine.submit(
            one["prompt"] if "prompt" in one else prompt_tokens(
                request["seed"], one["index"], one["prompt_len"],
                request["vocab"]).tolist(),
            one["steps"], logprobs=True) for one in request["requests"]]
        return {"results": [dict(zip(("tokens", "logprobs"),
                                     f.result(timeout=600)),
                                 ok=True, error=None) for f in futures]}


@pytest.mark.parametrize("fault", ["none", "window_sees_every_key",
                                   "edge_a_page_off",
                                   "plain_rope_on_full_layers"])
def test_the_harness_own_correct_under_a_planted_fault(fault, monkeypatch):
    """``Adapter.check_reference`` itself, on the tiny overlay cell (prompts
    of 5 and of 700 tokens), over a program with one of ISSUE 56's faults
    planted: each fails, the unfaulted program agrees."""
    import dataclasses

    from tpulab.engine import paged_steps
    from tpulab.models import spec as specs
    cell = spec.load_cell("tiny-mellum.closed", CELLS)
    adapter = cell.module("models", "mellum").Adapter(cell, 2**31 + 5,
                                                      lambda *_: None)
    said = []
    adapter.say = said.append
    true = adapter.spec
    if fault == "window_sees_every_key":
        served = dataclasses.replace(true, window=10 ** 6)
    elif fault == "edge_a_page_off":
        served = dataclasses.replace(true, window=true.window + 8)
    elif fault == "plain_rope_on_full_layers":
        served = dataclasses.replace(true, rope_scaling=(), rope_factor=1.0)
    else:
        served = true
    if fault in ("window_sees_every_key", "edge_a_page_off"):
        # the MASK alone is the faulty one: the tables move as the true
        # window has them (a wider window's rows are still held: the blocks
        # under it go back a block later)
        monkeypatch.setattr(
            specs.ModelSpec, "layer_window",
            lambda self, layer, inner=specs.ModelSpec.layer_window: (
                served.window if inner(true, layer) else 0))
    adapter.spec = served if fault == "plain_rope_on_full_layers" else true
    paged_steps._JIT_MEMO.clear()
    try:
        adapter.build()
        ok = adapter.check_reference(_Direct(adapter.engine))
    finally:
        adapter.shutdown()
        paged_steps._JIT_MEMO.clear()
    assert ok == (fault == "none"), said[-3:]
    # (the last line is the adapter's, at shutdown: the window group's
    # turnover over the run)
    assert "window group over the run" in said[-1]
    # the 700-token prompts were served again under load: four streams
    # among twelve other requests on four lanes
    loaded = [line for line in said if "under load" in line]
    assert len(loaded) == 1 and "12 other requests" in loaded[0]
    assert "0 window pages" not in loaded[0]
    if fault != "none":
        assert any("DISAGREES" in line for line in said)
    else:
        assert "agrees" in loaded[0]
