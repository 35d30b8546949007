"""Kind ``evabyte``: the reference against hand-unrolled cases, the rooflines'
counts against the issue's reckoning, the new readers on canned contexts, the
new cell's files, and a tiny overlay cell through ``perf/run.py`` end to end
on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "evabyte")
ROOFLINE = spec.load_module("rooflines", "evabyte")
EVA = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                  "evabyte-l8.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-evabyte.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ------------------------------------------------------- the reference ----

def test_chunk_summaries_against_two_chunks_pooled_by_hand():
    """Seven positions in chunks of three: two complete chunks, the seventh
    position in none; ``k~`` weighs the keys by softmax(mu . k), ``v~`` the
    VALUES by softmax(phi . k): both scored against the keys."""
    rng = np.random.default_rng(3)
    k, v = rng.standard_normal((2, 7, 2, 4))
    mu, phi = rng.standard_normal((2, 2, 4))
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    ks, vs = REF.chunk_summaries(f32(k), f32(v), f32(mu), f32(phi), 3)
    assert ks.shape == vs.shape == (2, 2, 4)
    for c in range(2):
        for h in range(2):
            kc, vc = k[3 * c:3 * c + 3, h], v[3 * c:3 * c + 3, h]
            a, b = np.exp(kc @ mu[h]), np.exp(kc @ phi[h])
            np.testing.assert_allclose(np.asarray(ks)[c, h],
                                       (a / a.sum()) @ kc, rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(np.asarray(vs)[c, h],
                                       (b / b.sum()) @ vc, rtol=2e-5,
                                       atol=2e-6)


def _tiny(rng, d=8, heads=2, ff=6, vocab=11, layers=1):
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.4, jnp.float32)
    params = {"embed": w(vocab, d), "final_norm": {"scale": 1 + w(d)},
              "lm_head": w(d, vocab), "mtp_heads": w(d, 2 * vocab)}
    for i in range(layers):
        params[f"layer{i}"] = {
            "ln1": {"scale": 1 + w(d)}, "ln2": {"scale": 1 + w(d)},
            "wqkv": w(d, 3 * d), "wo": w(d, d), "eva_mu": w(heads, d // heads),
            "eva_phi": w(heads, d // heads), "w1": w(d, ff), "w3": w(d, ff),
            "w2": w(ff, d)}
    return params


HYPER = dict(n_layers=1, rms_norm_eps=1e-5, rope_theta=1e4, n_heads=2,
             window=4, chunk=2)


def test_what_a_query_sees_by_the_definition():
    """One layer, windows of 4 in chunks of 2, by hand in float64: position
    3 attends rows 0-3; position 4 attends the two summaries of window 0
    and itself; position 9 the four summaries of windows 0 and 1 and rows
    8-9, in ONE softmax."""
    rng = np.random.default_rng(4)
    p = _tiny(rng)
    toks = rng.integers(0, 11, 10)
    got = REF.last_logits(p, toks, 10, **HYPER)
    f64 = lambda a: np.asarray(a, np.float64)               # noqa: E731
    lp = {k: (f64(v) if not isinstance(v, dict) else f64(v["scale"]))
          for k, v in p["layer0"].items()}
    norm = lambda x, s: x / np.sqrt((x * x).mean(-1, keepdims=True)   # noqa
                                    + 1e-5) * s
    x = f64(p["embed"])[toks]
    h = norm(x, lp["ln1"])
    q, k, v = (np.asarray(h @ lp["wqkv"][:, 8 * i:8 * i + 8]).reshape(
        10, 2, 4) for i in range(3))

    def rope(t):
        inv = 1e4 ** -(np.arange(2) / 2)
        ang = np.arange(10)[:, None] * inv[None]
        cos, sin = (np.concatenate([f(ang), f(ang)], -1)[:, None]
                    for f in (np.cos, np.sin))
        return t * cos + np.concatenate([-t[..., 2:], t[..., :2]], -1) * sin
    q, k = rope(q), rope(k)
    out = np.zeros((10, 2, 4))
    for hd in range(2):
        ks, vs = [], []
        for c in range(5):
            kc, vc = k[2 * c:2 * c + 2, hd], v[2 * c:2 * c + 2, hd]
            a = np.exp(kc @ lp["eva_mu"][hd])
            b = np.exp(kc @ lp["eva_phi"][hd])
            ks.append((a / a.sum()) @ kc)
            vs.append((b / b.sum()) @ vc)
        for i in range(10):
            w = i // 4
            keys = [k[j, hd] for j in range(4 * w, i + 1)] + ks[:2 * w]
            vals = [v[j, hd] for j in range(4 * w, i + 1)] + vs[:2 * w]
            s = np.exp(np.asarray(keys) @ q[i, hd] / 2.0)
            out[i, hd] = (s / s.sum()) @ np.asarray(vals)
    x = x + out.reshape(10, 8) @ lp["wo"]
    h = norm(x, lp["ln2"])
    g = h @ lp["w1"]
    x = x + (g / (1 + np.exp(-g)) * (h @ lp["w3"])) @ lp["w2"]
    want = norm(x, f64(p["final_norm"]["scale"])) @ f64(p["lm_head"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert got.shape == (10, 11)        # head 0's rows, not the three heads'


def test_blocks_of_queries_and_the_rows_a_server_would_hold():
    """The score matrix in blocks of 3 queries is the matrix whole; with
    ``stores`` layer 0's rows after 10 positions: 4 summaries (windows 0
    and 1), then rows 8 and 9."""
    rng = np.random.default_rng(5)
    p = _tiny(rng, layers=2)
    hyper = dict(HYPER, n_layers=2)
    toks = rng.integers(0, 11, 10)
    whole = REF.last_logits(p, toks, 4, **hyper)
    blocks, kv = REF.last_logits(p, toks, 4, block=3, stores=True, **hyper)
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-6)
    assert kv.shape == (2, 4 + 2, 8)
    # a sequence that ends ON a boundary holds its last window whole
    _, kv8 = REF.last_logits(p, toks[:8], 1, stores=True, **hyper)
    assert kv8.shape == (2, 2 + 4, 8)
    np.testing.assert_allclose(kv8[:, :2], kv[:, :2], rtol=1e-5, atol=1e-6)


def test_kv_error_and_summary_judge_the_largest():
    rng = np.random.default_rng(8)
    want = rng.standard_normal((2, 9, 8)).astype(np.float32)
    assert REF.kv_error(want.copy(), want) == 0.0
    off = want.copy()
    off[0, :4] *= 1.5            # four of nine key rows: under the median
    off[1] *= 1.25               # every value row
    assert REF.kv_error(off, want) == pytest.approx(0.25, rel=1e-5)
    assert REF.kv_error(want[:, :5], want) == float("inf")
    rounded = lambda a, e, m: np.asarray(jax.lax.reduce_precision(  # noqa
        jnp.asarray(a), e, m))
    assert REF.kv_error(rounded(want, 8, 7), want) < REF.KV_TOLERANCE / 3
    assert REF.kv_error(rounded(want, 4, 3), want) > REF.KV_TOLERANCE
    streams = [{"logprob_err": np.array([0.1, e]), "kv_err": e / 10,
                "argmax_gap": np.zeros(2)} for e in (0.2, 0.5, 0.3)]
    got = REF.summary(streams)
    assert (got["logprob_err_max"], got["kv_err"]) == (0.5, 0.05)
    assert got["logprob_err"] == pytest.approx(0.15)     # the median
    assert "kv_err" not in REF.summary([{k: s[k] for k in (
        "logprob_err", "argmax_gap")} for s in streams])
    assert 0 < REF.KV_TOLERANCE < REF.TOLERANCE < REF.MAX_TOLERANCE


# ------------------------------------------------------- the rooflines ----

def test_counts_are_the_issues_arithmetic():
    """A layer 202.4 M parameters; 8 layers with embedding and heads 1,631 M
    = 3.26 GB, all 32 layers 6.49 B = 12.98 GB; a row 131,072 B over 8
    layers; 3,968 rows at 32 k positions."""
    d, ff = 4096, 11008
    assert ROOFLINE.layer_params(EVA) == (4 * d * d + 3 * d * ff + 2 * d
                                          + 2 * 32 * 128) == 202_391_552
    assert ROOFLINE.head_params(EVA) == 8 * 320 * d
    assert ROOFLINE.model_params(EVA) == 1_630_932_992
    assert 3.26e9 < 2 * ROOFLINE.model_params(EVA) < 3.27e9
    full = dict(EVA, num_hidden_layers=32)
    assert 12.97e9 < 2 * ROOFLINE.model_params(full) < 12.99e9
    assert ROOFLINE.kv_bytes_per_row(EVA) == 8 * 2 * 32 * 128 * 2 == 131_072
    assert [ROOFLINE.rows_of(EVA, n) for n in (0, 1, 2048, 2049, 32768)] == [
        0, 1, 2048, 129, 15 * 128 + 2048]
    # the pool: 16 lanes x 4,096 rows; the same lanes on a dense cache
    assert 16 * 4096 * 131_072 == 8_589_934_592
    assert 16 * 32768 * 131_072 > 68.7e9


def test_decode_step_bytes_by_hand():
    """16 lanes at 1,800 rows: 3.77 GB of rows beside 3.24 GB of layers and
    the ONE head that is read."""
    got = ROOFLINE.decode_step_bytes(EVA, 16, 1800)
    assert got == 2 * (8 * 202_391_552 + 320 * 4096) + 16 * 1800 * 131_072
    assert 7.0e9 < got < 7.05e9
    assert ROOFLINE.decode_step_bytes(EVA, 0, 0) == 2 * (
        8 * 202_391_552 + 320 * 4096)
    cost = ROOFLINE.summary_cost(EVA)
    assert cost["bytes"] == (2048 + 128) * 131_072
    assert cost["flops"] == 8 * 2048 * 32 * 8 * 128
    assert ROOFLINE.summary_cost(EVA, 3)["bytes"] == 3 * cost["bytes"]


# -------------------------------------------------------- the readers ----

class _Cell:
    config = EVA

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None):
    pool = {"n_pages": 4097, "page_size": 16, "page_nbytes": 16 * 131_072}

    def eva(scale):
        return {"window": 2048, "chunk": 16, "compactions": {
                    "round": 30 * scale, "decode": 10 * scale},
                "rows_compacted": 40 * 2048 * scale, "pages_released": 4800,
                "compact_s": 0.06 * scale, "summary_rows_live": 0,
                "raw_rows_live": 0}

    def dispatch(scale):
        # 15 lanes a decode step at 1,800 rows a lane, 600 of them summaries
        return {"decode_block_steps": 100 * scale, "kinds": {
                    "decode": 50 * scale, "mixed": 400 * scale, "verify": 0},
                "lane_work": {
                    "decode": {"passes": 1500 * scale, "rows": 1500 * scale,
                               "keys": 1500 * 1800 * scale,
                               "summary_keys": 1500 * 600 * scale},
                    "round": {"passes": 800 * scale, "rows": 9000 * scale,
                              "keys": 800 * 1500 * scale,
                              "summary_keys": 800 * 500 * scale}}}
    gauges = [{"n_pages": 4097, "free_pages": 3000, "decode_pages": 1200,
               "decode_positions": 150_000},
              {"n_pages": 4097, "free_pages": 2900, "decode_pages": 1000,
               "decode_positions": 170_000},
              {"n_pages": 4097, "free_pages": 4096, "decode_pages": 0,
               "decode_positions": 0}]
    return {"cell": _Cell, "trace": trace, "gauges": gauges,
            "counters_before": {"pool": pool, "eva": eva(1),
                                "dispatch": dispatch(1)},
            "counters_after": {"pool": pool, "eva": eva(3),
                               "dispatch": dispatch(3)}}


def _read(name, ctx):
    return spec.load_module("layer_metrics", name).read(ctx)


def test_new_readers_on_a_canned_context():
    ctx = _ctx()
    assert _read("eva.summary_keys_share", ctx) == pytest.approx(100 / 3)
    assert _read("eva.cache_bytes_per_position", ctx) == pytest.approx(
        2200 * 16 * 131_072 / 320_000)
    assert _read("eva.compact_ms", ctx) == pytest.approx(1e3 * 0.12 / 80)
    for name in ("eva.decode_roofline", "eva.summary_roofline"):
        assert _read(name, ctx) is None                         # no trace
    # a program without the counters (the parent) or another model: nothing
    # to read, no error
    for old in ({"dispatch": {}}, {"dispatch": {"lane_work": {
            "decode": {"passes": 3, "rows": 3, "keys": 9}}}, "pool": {}}):
        bare = {"cell": _Cell, "trace": {"modules": {
            "jit_paged_decode_block_k2": {"durations_s": [0.02]}}},
            "gauges": [{"n_pages": 9, "free_pages": 1}],
            "counters_before": old, "counters_after": old}
        for name in ("eva.summary_keys_share", "eva.cache_bytes_per_position",
                     "eva.compact_ms", "eva.decode_roofline",
                     "eva.summary_roofline"):
            assert _read(name, bare) is None, name
    # a window without a compaction
    still = _ctx()
    still["counters_after"]["eva"] = still["counters_before"]["eva"]
    assert _read("eva.compact_ms", still) is None


def test_rooflines_are_bytes_over_bandwidth_over_mean_time(monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.026, 0.028]},
        "jit_paged_decode_block_k1": {"durations_s": [0.014]},
        "jit_paged_mixed_step": {"durations_s": [0.02]},
        "jit_paged_eva_compact": {"durations_s": [0.001, 0.003]}}}
    ctx = _ctx(trace)
    step = (0.026 + 0.028 + 0.014) / 5
    assert _read("eva.decode_roofline", ctx) == pytest.approx(
        100 * ROOFLINE.decode_step_bytes(EVA, 15, 1800) / 819e9 / step)
    assert _read("eva.summary_roofline", ctx) == pytest.approx(
        100 * ROOFLINE.summary_cost(EVA)["bytes"] / 819e9 / 0.002)
    assert 0 < _read("eva.decode_roofline", ctx) < 100
    assert 0 < _read("eva.summary_roofline", ctx) < 100
    del trace["modules"]["jit_paged_eva_compact"]
    assert _read("eva.summary_roofline", ctx) is None     # none in the slice


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_widths():
    cell = spec.load_cell("evabyte-l8.bytes-longdoc")
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "evabyte-l8", "bytes-longdoc-closed-c16")
    c = cell.config
    assert c["kind"] == "evabyte" and c["reduced"].keys() == {
        "num_hidden_layers"}
    assert {"assumed", "departures", "stands_for", "source"} <= set(c)
    assert cell.traffic["engine"] == {"lanes": 16, "max_len": 32768,
                                      "page_size": 16, "pool_tokens": 65536}
    assert cell.traffic["concurrency"] == cell.traffic["set_size"] == 16
    assert cell.traffic["reference_prompt_lens"] == [24, 4090]
    names = {m["name"] for m in cell.per_layer}
    assert {"eva.summary_keys_share", "eva.cache_bytes_per_position",
            "eva.compact_ms", "eva.decode_roofline",
            "eva.summary_roofline", "kv.pages_in_use_peak"} <= names
    assert not {"kv.bytes_per_token", "gdn.decode_roofline",
                "ssm.decode_roofline", "dsa.decode_roofline"} & names
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "evabyte")
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "EvaByte")
        differs = {k for k, v in row["config"].items() if c[k] != v}
        assert differs == set(c["reduced"]) == {"num_hidden_layers"}
        assert c["source"] == row["source_url"]


def test_the_mix_is_the_issues_and_no_operation_can_fail():
    """Prompts 6,144-25,886 bytes, outputs 586-1,791: a lane's pool share
    covers the most ROWS the longest pair holds, ``max_len`` its
    positions; every prompt is past one window."""
    from harness.sizes import size_pairs
    traffic = spec.load_json(os.path.join(
        spec.PERF_DIR, "traffic", "bytes-longdoc-closed-c16.json"))
    pairs = size_pairs(traffic, 16)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (6144, 25886)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (586, 1791)
    eng = traffic["engine"]
    assert pairs.sum(1).max() <= eng["max_len"]
    # the most rows ANY lane of max_len positions holds (a whole last
    # window behind fifteen windows of summaries) fit a lane's share
    assert ROOFLINE.rows_of(EVA, eng["max_len"]) == 3968 <= (
        eng["pool_tokens"] // eng["lanes"])
    assert pairs[:, 0].min() > EVA["window_size"]
    assert eng["page_size"] == EVA["chunk_size"]
    assert traffic["generator"] == "closed_replay"


def test_adapter_draws_queries_keys_and_scorers_by_the_stated_rule():
    adapter = spec.load_module("models", "evabyte")
    sp = adapter.spec_of(EVA)
    assert (sp.eva_window, sp.eva_chunk, sp.n_layers, sp.pred_heads) == (
        2048, 16, 8, 8)
    key = jax.random.key(1, impl="rbg")
    rule = lambda path, shape: np.asarray(      # noqa: E731
        adapter.fill_rule(path, shape, key, sp))
    mu = rule("['layer0']['eva_mu']", (32, 128))
    assert np.abs(mu).max() <= 2 and 0.8 < mu.std() < 0.95
    assert (rule("['layer0']['ln1']['scale']", (8,)) == 1).all()
    w = rule("['layer0']['wqkv']", (4096, 3 * 4096))
    # a normed input (unit mean square) gives q of deviation 10, k of 0.2
    assert w[:, :4096].std() * 64 == pytest.approx(adapter.Q_STD, rel=0.02)
    assert w[:, 4096:8192].std() * 64 == pytest.approx(adapter.K_STD,
                                                       rel=0.02)
    assert 0.0195 < w[:, 8192:].std() < 0.0205
    assert 0.0195 < rule("['layer0']['w1']", (512, 512)).std() < 0.0205


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_evabyte_cell_end_to_end_on_the_cpu():
    """Windows of 256 in chunks of 16: prompts of 260-700 compact once or
    twice in rounds, and the reference's prompt of 500 crosses position 512
    in decode."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-evabyte.closed", "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    m = out["metrics"]
    assert "eva_window=256 eva_chunk=16" in proc.stdout
    assert "ragged=True" in proc.stdout and "kv_err=" in proc.stdout
    assert 0 < m["eva.summary_keys_share"]["value"] < 100
    # 2 layers x (K + V) x 64 x 2 B = 512 B a row; a position costs less
    assert 0 < m["eva.cache_bytes_per_position"]["value"] < 512
    assert m["eva.compact_ms"]["value"] > 0
    assert 0 < m["sched.mixed_round_share"]["value"] <= 100
    assert m["compiles_in_window.lm"]["value"] == 0
    assert "eva.decode_roofline" not in m     # no TPU trace on a CPU
    assert "eva.summary_roofline" not in m
