"""Kind ``jamba``: the reference against a hand-unrolled two-token case, the
roofline's byte count against the issue's reckoning, the new readers on canned
contexts, the new cell's files, and a tiny overlay cell through
``perf/run.py`` end to end on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "jamba")
ROOFLINE = spec.load_module("rooflines", "jamba")
JAMBA = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                    "jamba2-3b.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-jamba.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ------------------------------------------------------- the reference ----

def _mamba_leaves(rng, d, di, n, k, r):
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    return {"in_proj": w(d, 2 * di), "conv_w": w(k, di), "conv_b": w(di),
            "x_proj": w(di, r + 2 * n),
            "dt_norm": {"scale": 1 + w(r)}, "b_norm": {"scale": 1 + w(n)},
            "c_norm": {"scale": 1 + w(n)}, "dt_proj": w(r, di),
            "dt_bias": w(di) - 2, "a_log": w(n, di), "d": 1 + w(di),
            "out_proj": w(di, d)}


def test_mamba_mixer_against_two_tokens_unrolled_by_hand():
    """Two tokens in float64 numpy, every sum written out: token 0 sees
    zeros before the sequence and an empty state, token 1 sees token 0 in
    its convolution (tap ``d_conv - 2``) and through the state."""
    d, di, n, k, r, eps = 6, 8, 4, 4, 3, 1e-6
    p = _mamba_leaves(np.random.default_rng(3), d, di, n, k, r)
    h = np.random.default_rng(4).standard_normal((2, d))
    f64 = lambda a: np.asarray(a, np.float64)
    norm = lambda v, s: v / np.sqrt((v ** 2).mean() + eps) * f64(s)
    silu = lambda v: v / (1 + np.exp(-v))
    softplus = lambda v: np.log1p(np.exp(v))
    uz = h @ f64(p["in_proj"])
    u_in, z = uz[:, :di], uz[:, di:]
    w, a = f64(p["conv_w"]), -np.exp(f64(p["a_log"]))
    u = [silu(f64(p["conv_b"]) + w[k - 1] * u_in[0]),
         silu(f64(p["conv_b"]) + w[k - 1] * u_in[1] + w[k - 2] * u_in[0])]
    state, want = np.zeros((n, di)), []
    for t in range(2):
        xp = u[t] @ f64(p["x_proj"])
        dt = softplus(norm(xp[:r], p["dt_norm"]["scale"])
                      @ f64(p["dt_proj"]) + f64(p["dt_bias"]))
        b = norm(xp[r:r + n], p["b_norm"]["scale"])
        c = norm(xp[r + n:], p["c_norm"]["scale"])
        state = np.exp(dt[None, :] * a) * state + np.outer(b, dt * u[t])
        y = c @ state + f64(p["d"]) * u[t]
        want.append((y * silu(z[t])) @ f64(p["out_proj"]))
    got = REF.mamba_mixer(jnp.asarray(h, jnp.float32), p, eps=eps)
    np.testing.assert_allclose(np.asarray(got), np.stack(want), rtol=2e-5,
                               atol=2e-5)


def test_attention_mixer_has_no_positional_encoding_and_is_causal():
    """One KV head under four query heads: permuting the context of the
    last query leaves its output alone (no positions), and no query reads
    a later token."""
    rng = np.random.default_rng(5)
    t, d, heads = 5, 8, 4
    wqkv = jnp.asarray(rng.standard_normal((d, d + 2 * 2)), jnp.float32)
    wo = jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
    h = rng.standard_normal((t, d)).astype(np.float32)
    run = lambda x: np.asarray(REF.attention_mixer(
        jnp.asarray(x), wqkv, wo, n_heads=heads, n_kv_heads=1, block=2))
    out = run(h)
    np.testing.assert_allclose(run(h[[2, 0, 3, 1, 4]])[4], out[4], rtol=1e-5,
                               atol=1e-5)
    later = h.copy()
    later[3:] += 1.0
    np.testing.assert_allclose(run(later)[:3], out[:3], rtol=1e-6, atol=1e-6)


def test_hyper_of_reads_the_layer_order():
    hyper = REF.hyper_of(JAMBA)
    assert hyper["attention_layers"] == (7, 21) and hyper["n_layers"] == 28
    assert (hyper["n_heads"], hyper["n_kv_heads"]) == (20, 1)
    assert REF.REFERENCE_STEPS == 32 and 0 < REF.TOLERANCE < 1


# -------------------------------------------------------- the rooflines ----

def test_parameter_and_state_counts_are_the_issues():
    """ISSUE 32's own count: a Mamba mixer 41.24 M, an attention mixer
    13.76 M, 3,029 M parameters = 6.06 GB, 9.32 MB of state a lane."""
    assert ROOFLINE.mamba_params(JAMBA) == (
        2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 5120 * 2560) == 41_241_600
    assert ROOFLINE.attention_params(JAMBA) == 13_762_560
    assert ROOFLINE.n_attention_layers(JAMBA) == 2
    assert ROOFLINE.model_params(JAMBA) == (
        26 * (41_241_600 + 62_914_560) + 2 * (13_762_560 + 62_914_560)
        + 65536 * 2560) == 3_029_186_560
    assert ROOFLINE.state_bytes_per_lane(JAMBA) == 9_318_400
    got = ROOFLINE.decode_step_bytes(JAMBA, 32)
    assert got == 2 * 3_029_186_560 + 2 * 32 * 9_318_400
    assert 6.65e9 < got < 6.66e9
    # the weights alone, whatever the batch
    assert ROOFLINE.decode_step_bytes(JAMBA, 0) == pytest.approx(6.058e9,
                                                                  rel=1e-3)


def test_selective_scan_cost_counts_rows_states_and_channels():
    cost = ROOFLINE.selective_scan_cost(JAMBA, rows=288, segments=34)
    assert cost["exps"] == 288 * 16 * 5120 == 23_592_960
    assert cost["flops"] == 6 * cost["exps"] + 3 * 288 * 5120
    assert cost["bytes"] == 4 * (3 * 288 * 5120 + 2 * 288 * 16
                                 + 2 * 34 * 16 * 5120)


# -------------------------------------------------------- the readers ----

class _Cell:
    config = JAMBA

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None, lanes=(32, 30, 31)):
    state = {"kind": "mamba", "lanes": 32, "bytes_per_lane": 9_318_400,
             "hbm_bytes": 32 * 9_318_400, "zero_starts": 7}
    return {"cell": _Cell, "trace": trace,
            "gauges": [{"active_lanes": n} for n in lanes],
            "counters_before": {"state": dict(state, zero_starts=0)},
            "counters_after": {"state": state}}


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    assert read("ssm.state_bytes_per_lane", _ctx()) == 9_318_400
    assert read("ssm.decode_roofline", _ctx()) is None          # no trace
    # a program without the counters (the parent): nothing to read, no error
    old = {"cell": _Cell, "trace": {"modules": {}}, "gauges": [],
           "counters_before": {"dispatch": {}},
           "counters_after": {"dispatch": {}}}
    for name in ("ssm.state_bytes_per_lane", "ssm.decode_roofline"):
        assert read(name, old) is None


def test_decode_roofline_is_bytes_over_bandwidth_over_mean_step(monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.020, 0.024]},
        "jit_paged_decode_block_k1": {"durations_s": [0.011]},
        "jit_paged_mixed_step": {"durations_s": [0.5]}}}
    got = spec.load_module("layer_metrics",
                           "ssm.decode_roofline").read(_ctx(trace))
    mean_step = (0.020 + 0.024 + 0.011) / (2 + 2 + 1)
    want = 100 * ROOFLINE.decode_step_bytes(JAMBA, 31.0) / 819e9 / mean_step
    assert got == pytest.approx(want) and 0 < got < 100


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves_and_keeps_the_published_keys():
    cell = spec.load_cell("jamba2-3b.reason")
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "jamba2-3b", "reason-closed-c32")
    assert cell.config["kind"] == "jamba" and cell.config["reduced"] == {}
    assert cell.traffic["engine"] == {"lanes": 32, "max_len": 8192,
                                      "page_size": 16, "pool_tokens": 131072}
    assert cell.traffic["concurrency"] == cell.traffic["set_size"] == 32
    assert cell.traffic["reference_prompt_lens"] == [24, 600]
    names = [m["name"] for m in cell.per_layer]
    assert {"ssm.state_bytes_per_lane", "ssm.decode_roofline",
            "kv.pages_in_use_peak", "sched.ahead_share"} <= set(names)
    assert "moe.experts_hit_per_step" not in names
    for kind, name in (("models", "jamba"), ("reference", "jamba"),
                       ("rooflines", "jamba"),
                       ("loadgen", cell.traffic["generator"])):
        cell.module(kind, name)
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "AI21-Jamba2-3B")
        assert {k: cell.config[k] for k in row["config"]} == row["config"]
        assert cell.config["source"] == row["source_url"]


def test_the_mix_is_the_issues_and_no_operation_can_fail():
    """Prompts 54-303 and outputs 335-1221 in 32 sizes: a lane's pool share
    (4096 tokens) and max_len cover the longest pair."""
    from harness.sizes import size_pairs
    traffic = spec.load_json(os.path.join(spec.PERF_DIR, "traffic",
                                          "reason-closed-c32.json"))
    pairs = size_pairs(traffic, 32)
    assert (pairs[:, 0].min(), pairs[:, 0].max()) == (54, 303)
    assert (pairs[:, 1].min(), pairs[:, 1].max()) == (335, 1221)
    eng = traffic["engine"]
    assert pairs.sum(1).max() <= eng["pool_tokens"] // eng["lanes"]
    assert pairs.sum(1).max() <= eng["max_len"]


def test_adapter_fills_the_ssm_leaves_by_the_published_rule():
    """The adapter's device-side fill draws what ``init_params`` draws: by
    name, not normal 0.02 for the SSM leaves."""
    adapter = spec.load_module("models", "jamba")
    key = jax.random.key(1, impl="rbg")
    a = adapter.fill_rule("['layer0']['mamba']['a_log']", (16, 8), key, 4)
    np.testing.assert_allclose(np.exp(np.asarray(a))[:, 3],
                               np.arange(1, 17), rtol=1e-6)
    assert (np.asarray(adapter.fill_rule("['layer0']['mamba']['d']", (8,),
                                         key, 4)) == 1).all()
    assert (np.asarray(adapter.fill_rule(
        "['layer0']['mamba']['dt_norm']['scale']", (6,), key, 4)) == 1).all()
    dt = np.asarray(jax.nn.softplus(adapter.fill_rule(
        "['layer0']['mamba']['dt_bias']", (4096,), key, 4)))
    assert 1e-3 * 0.99 <= dt.min() < 2e-3 and 5e-2 < dt.max() <= 1e-1 * 1.01
    conv = np.asarray(adapter.fill_rule("['layer0']['mamba']['conv_w']",
                                        (4, 1024), key, 4))
    assert 0.45 < np.abs(conv).max() <= 0.5
    w = np.asarray(adapter.fill_rule("['layer0']['mamba']['in_proj']",
                                     (64, 256), key, 4))
    assert 0.018 < w.std() < 0.022


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_jamba_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-jamba.closed", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    m = out["metrics"]
    assert "pool_layers=2" in proc.stdout and "ragged=True" in proc.stdout
    # 3 Mamba layers x (8 x 128 float32 + 3 x 128 bf16); 2 attention layers
    assert m["ssm.state_bytes_per_lane"]["value"] == 3 * (4096 + 768)
    assert m["kv.bytes_per_token"]["value"] == 2 * 2 * 16 * 2
    assert 0 < m["sched.mixed_round_share"]["value"] <= 100
    assert "ssm.decode_roofline" not in m     # no TPU trace on a CPU
