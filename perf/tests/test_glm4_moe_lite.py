"""Kind ``glm4_moe_lite``: the reference against a hand-expanded layer, the
roofline's byte count, the new readers on canned contexts, and a tiny
overlay cell through ``perf/run.py`` end to end on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "glm4_moe_lite")
ROOFLINE = spec.load_module("rooflines", "glm4_moe_lite")
GLM = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                  "glm47flash-l8.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-glm.json")
TINY = spec.load_json(os.path.join(spec.PERF_DIR, "tests", "cells", "configs",
                                   "tiny-glm.json"))


# ------------------------------------------------------- the reference ----

def _numpy_layer(p, x, eps, theta, top_k, scale):
    """One expert layer written out by hand in float64 numpy: a loop over
    positions, heads and experts, keys and values expanded per head."""
    f64 = lambda a: np.asarray(a, np.float64)
    norm = lambda v, s: v / np.sqrt((v ** 2).mean(-1, keepdims=True) + eps) \
        * f64(s)
    t = x.shape[0]
    w_uk, w_uv = f64(p["w_uk"]), f64(p["w_uv"])
    n_heads, nope, c = w_uk.shape

    def rope(v, pos):
        half = v.shape[-1] // 2
        ang = pos / theta ** (np.arange(half) / half)
        cos, sin = np.cos(ang), np.sin(ang)
        a, b = v[:half], v[half:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin])

    h = norm(x, p["ln1"]["scale"])
    q = (norm(h @ f64(p["wq_a"]), p["q_norm"]["scale"]) @ f64(p["wq_b"])
         ).reshape(t, n_heads, -1)
    kva = h @ f64(p["wkv_a"])
    ckv = norm(kva[:, :c], p["kv_norm"]["scale"])
    k_r = np.stack([rope(kva[i, c:], i) for i in range(t)])
    attn = np.zeros((t, n_heads, w_uv.shape[-1]))
    for i in range(t):
        for hh in range(n_heads):
            qi = np.concatenate([q[i, hh, :nope], rope(q[i, hh, nope:], i)])
            keys = np.stack([np.concatenate([w_uk[hh] @ ckv[j], k_r[j]])
                             for j in range(i + 1)])
            s = keys @ qi / np.sqrt(qi.size)
            pr = np.exp(s - s.max())
            pr /= pr.sum()
            attn[i, hh] = pr @ np.stack([ckv[j] @ w_uv[hh]
                                         for j in range(i + 1)])
    x = x + attn.reshape(t, -1) @ f64(p["wo"])
    h = norm(x, p["ln2"]["scale"])
    silu = lambda v: v / (1 + np.exp(-v))
    swiglu = lambda v, g, u, d: (silu(v @ f64(g)) * (v @ f64(u))) @ f64(d)
    m, sh = p["moe"], p["shared"]
    out = swiglu(h, sh["w1"], sh["w3"], sh["w2"])
    s = 1 / (1 + np.exp(-(h @ f64(m["router"]))))
    f = m["w2"].shape[1]
    for i in range(t):
        chosen = np.argsort(-(s[i] + f64(m["bias"])), kind="stable")[:top_k]
        w = s[i, chosen] / (s[i, chosen].sum() + 1e-20) * scale
        for e, we in zip(chosen, w):
            w13 = f64(m["w13"][e])
            out[i] += we * swiglu(h[i], w13[:, :f], w13[:, f:], m["w2"][e])
    return x + out


def test_reference_layer_equals_the_hand_expanded_layer():
    from tpulab.models.spec import glm4_moe_lite_spec, init_params
    cfg = dict(TINY, num_hidden_layers=1, first_k_dense_replace=0)
    params = init_params(glm4_moe_lite_spec(cfg), 256, 96, seed=5, scale=0.15)
    p = params["layer0"]
    x = np.random.default_rng(0).standard_normal((9, 64))
    want = _numpy_layer(p, x, 1e-5, 1e6, 2, 1.8)
    xj = jnp.asarray(x, jnp.float32)
    got = REF._ffn(REF._attention(
        xj, {k: p[k] for k in ("ln1", "wq_a", "q_norm", "wq_b", "wkv_a",
                               "kv_norm", "w_uk", "w_uv", "wo")},
        eps=1e-5, theta=1e6, block=4), p, eps=1e-5, top_k=2, scale=1.8,
        norm=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_reference_compare_catches_a_wrong_model():
    from tpulab.models.spec import glm4_moe_lite_spec, init_params
    params = init_params(glm4_moe_lite_spec(TINY), 256, 96, seed=1, scale=0.15)
    hyper = REF.hyper_of(TINY)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, 20).tolist()
    tokens, logprobs = [], []
    for _ in range(5):
        row = REF.last_logits(params, prompt + tokens, 1, **hyper)[0]
        row = row.astype(np.float64)
        tokens.append(int(row.argmax()))
        logprobs.append(float(row.max() - np.log(np.exp(row).sum())))
    good = REF.compare(params, prompt, tokens, logprobs, **hyper)
    assert good["argmax_gap"] == 0 and good["logprob_err_max"] < 1e-4
    assert good["flipped_share"] == 0
    # without the scaling of the routed experts it is another model: the
    # lower quartile over the tokens, which is what is judged, passes it
    bad = REF.compare(params, prompt, tokens, logprobs,
                      **dict(hyper, routed_scaling_factor=1.0))
    assert bad["logprob_err"] > REF.TOLERANCE
    # the selection bias decides which experts run
    shifted = jax.tree_util.tree_map(lambda a: a, params)
    for i in (1, 2):
        shifted[f"layer{i}"]["moe"]["bias"] = -params[f"layer{i}"]["moe"][
            "bias"] * 20
    assert REF.compare(shifted, prompt, tokens, logprobs,
                       **hyper)["logprob_err"] > REF.TOLERANCE


def test_reference_imports_nothing_from_the_program():
    src = open(os.path.join(spec.PERF_DIR, "reference",
                            "glm4_moe_lite.py")).read()
    assert "import tpulab" not in src and "from tpulab" not in src


# ------------------------------------------------------- the roofline ----

def test_decode_step_bytes_at_the_published_widths():
    assert ROOFLINE.attention_params(GLM) == 21_757_952       # 21.76 M
    assert ROOFLINE.expert_params(GLM) == 9_437_184           # 9.437 M
    none = ROOFLINE.decode_step_bytes(GLM, 0)
    # 8 x attention + the dense layer's FFN + 7 x (router + shared) + head
    assert none == 2 * (8 * 21_757_952 + 3 * 2048 * 10240
                        + 7 * (2048 * 64 + 9_437_184) + 2048 * 154880)
    # every expert hit: all of the model but its embedding (norm scales
    # and the selection bias apart): 10.33 GB - 0.63 GB
    every = ROOFLINE.decode_step_bytes(GLM, 64)
    assert every == none + 2 * 7 * 64 * 9_437_184
    assert 9.69e9 < every < 9.71e9
    # the issue's reckoning: ~26 of 64 hit at 8 lanes -> 4.6-4.7 GB
    assert 4.6e9 < ROOFLINE.decode_step_bytes(GLM, 26) < 4.7e9


def test_latent_attention_cost_counts_the_causal_triangle():
    one = ROOFLINE.latent_attention_cost(GLM, [1], [100], 16, 640)
    assert one["flops"] == 2 * 20 * 100 * (576 + 512)
    assert one["bytes"] == 2 * (7 * 16 * 640 + 20 * 576 + 20 * 512)
    chunk = ROOFLINE.latent_attention_cost(GLM, [4, 0], [4, 0], 16, 640)
    assert chunk["flops"] == 2 * 20 * (1 + 2 + 3 + 4) * (576 + 512)


# -------------------------------------------------------- the readers ----

class _Cell:
    config = GLM

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _ctx(trace=None):
    moe0 = {"expert_layers": [1, 2], "assignments": [[5] * 4, [1] * 4],
            "decode_steps": 10, "experts_hit": 50}
    moe1 = {"expert_layers": [1, 2],
            "assignments": [[5 + 10, 5 + 30, 5 + 20, 5 + 20],
                            [1 + 20, 1 + 20, 1 + 20, 1 + 20]],
            "decode_steps": 110, "experts_hit": 50 + 100 * 2 * 3}
    pool = {"n_pages": 2049, "page_size": 16, "hbm_bytes": 2049 * 16 * 10240}
    return {"cell": _Cell, "trace": trace,
            "counters_before": {"moe": moe0, "pool": pool},
            "counters_after": {"moe": moe1, "pool": pool}}


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx()
    assert read("kv.bytes_per_token", ctx) == 10240
    assert read("moe.expert_load_max_over_mean", ctx) == 30 * 4 / 80
    assert read("moe.experts_hit_per_step", ctx) == 3.0
    assert read("step.decode_weight_roofline", ctx) is None    # no trace
    # a program without the counters (the parent): nothing to read, no error
    old = {"cell": _Cell, "trace": None, "counters_before": {"dispatch": {}},
           "counters_after": {"dispatch": {}}}
    for name in ("kv.bytes_per_token", "moe.expert_load_max_over_mean",
                 "moe.experts_hit_per_step", "step.decode_weight_roofline"):
        assert read(name, old) is None


def test_decode_weight_roofline_is_bytes_over_bandwidth_over_mean_step(
        monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.020, 0.024]},
        "jit_paged_decode_block_k1": {"durations_s": [0.016]},
        "jit_paged_mixed_step": {"durations_s": [0.5]}}}
    got = spec.load_module("layer_metrics",
                           "step.decode_weight_roofline").read(_ctx(trace))
    mean_step = (0.020 + 0.024 + 0.016) / (2 + 2 + 1)
    want = 100 * ROOFLINE.decode_step_bytes(GLM, 3.0) / 819e9 / mean_step
    assert got == pytest.approx(want) and 0 < got < 100


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_glm_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-glm.closed", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    m = out["metrics"]
    assert "entry=latent" in proc.stdout and "ragged=True" in proc.stdout
    assert m["kv.bytes_per_token"]["value"] == 3 * 128 * 2     # bf16 rows
    assert 1 <= m["moe.experts_hit_per_step"]["value"] <= 8
    assert m["moe.expert_load_max_over_mean"]["value"] >= 1
    assert 0 < m["sched.mixed_round_share"]["value"] <= 100
    assert "step.decode_weight_roofline" not in m     # no TPU trace on a CPU
