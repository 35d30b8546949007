"""Kind ``keye_vl2``: the reference against a hand-worked case and its own
tiling, the roofline's counts, the new readers on canned contexts, the new
cell's files, and a tiny overlay cell through ``perf/run.py`` end to end on
the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

REF = spec.load_module("reference", "keye_vl2")
ROOFLINE = spec.load_module("rooflines", "keye_vl2")
KEYE = spec.load_json(os.path.join(spec.PERF_DIR, "configs",
                                   "keyevl2-l6.json"))
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench-keye.json")
TINY = spec.load_json(os.path.join(spec.PERF_DIR, "tests", "cells", "configs",
                                   "tiny-keye.json"))


# ------------------------------------------------------- the reference ----

def test_index_scores_and_selection_on_a_hand_worked_three_key_case():
    """Two index heads of width 2, three tokens, ``topk`` 2, worked by
    hand: ``I_ts = (2 * 2)^-0.5 * sum_i c_ti * relu(a_ti . b_s)``."""
    a = jnp.asarray([[[1, 0], [0, 1]], [[1, 1], [1, -1]], [[2, 0], [0, -1]]],
                    jnp.float32)
    b = jnp.asarray([[1, 2], [3, -1], [-1, 1]], jnp.float32)
    c = jnp.asarray([[1, 1], [2, -1], [1, 3]], jnp.float32)
    got = np.asarray(REF.index_scores(a, b, c, 2, 2))
    # token 0: key 0: 1*relu(1) + 1*relu(2) = 3
    # token 1: key 0: 2*relu(3) - relu(-1) = 6; key 1: 2*relu(2) - relu(4) = 0
    # token 2: key 0: relu(2) + 3*relu(-2) = 2; key 1: relu(6) + 3*relu(1) = 9;
    #          key 2: relu(-2) + 3*relu(-1) = 0
    want = 0.5 * np.array([[3, -np.inf, -np.inf], [6, 0, -np.inf], [2, 9, 0]])
    np.testing.assert_allclose(got, want)
    chosen = np.asarray(REF.selection(jnp.asarray(got), 2))
    np.testing.assert_array_equal(chosen, [[1, 0, 0], [1, 1, 0], [1, 1, 0]])
    # ties go to the lower key: token 2 with keys 0 and 2 tied under key 1
    tied = jnp.asarray(0.5 * np.array([[3, -np.inf, -np.inf],
                                       [6, 0, -np.inf], [2, 9, 2]]))
    np.testing.assert_array_equal(np.asarray(REF.selection(tied, 2))[2],
                                  [1, 1, 0])


def test_the_tiles_leave_the_index_scores_unchanged():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((37, 4, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((37, 16)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((37, 4)), jnp.float32)
    whole = np.asarray(REF.index_scores(a, b, c, 64, 64))
    for q_chunk, kv_chunk in ((8, 8), (5, 16), (37, 3)):
        np.testing.assert_allclose(
            np.asarray(REF.index_scores(a, b, c, q_chunk, kv_chunk)), whole,
            rtol=1e-6, atol=1e-6)
    assert np.isneginf(whole[np.triu_indices(37, 1)]).all()


def _tiny_params(seed=1):
    from tpulab.models.spec import init_params, keye_vl2_spec
    return init_params(keye_vl2_spec(TINY), 256, 0, seed=seed, scale=0.3)


def test_reference_attends_to_the_selected_keys_alone():
    """Past ``topk`` the logits differ from full attention's, and equal
    them where ``topk`` covers the context."""
    params = _tiny_params()
    hyper = REF.hyper_of(TINY)
    toks = np.random.default_rng(1).integers(0, 256, 50).tolist()
    sparse = REF.last_logits(params, toks, 1, **hyper)
    full = REF.last_logits(params, toks, 1, **dict(hyper, index_topk=64))
    assert np.abs(sparse - full).max() > 1e-3
    short = toks[:30]
    np.testing.assert_allclose(
        REF.last_logits(params, short, 1, **hyper),
        REF.last_logits(params, short, 1, **dict(hyper, index_topk=64)),
        rtol=1e-5, atol=1e-5)


def test_reference_compare_catches_a_wrong_selection_and_names_the_limit():
    params = _tiny_params()
    hyper = REF.hyper_of(TINY)
    prompt = np.random.default_rng(2).integers(0, 256, 45).tolist()
    tokens, logprobs = [], []
    for _ in range(5):
        row = REF.last_logits(params, prompt + tokens, 1, **hyper)[0]
        row = row.astype(np.float64)
        tokens.append(int(row.argmax()))
        logprobs.append(float(row.max() - np.log(np.exp(row).sum())))
    good = REF.compare(params, prompt, tokens, logprobs, **hyper)
    assert good["argmax_gap"] == 0 and good["logprob_err_max"] < 1e-4
    # a narrower selection is another model
    bad = REF.compare(params, prompt, tokens, logprobs,
                      **dict(hyper, index_topk=8))
    assert bad["logprob_err"] > REF.TOLERANCE
    assert REF.tolerance(32, **hyper) == REF.TOLERANCE_DENSE
    assert REF.tolerance(33, **hyper) == REF.TOLERANCE
    assert REF.TOLERANCE_DENSE < REF.TOLERANCE
    assert set(REF.TOLERANCE_READINGS) >= {
        "bf16", "newest_window", "one_selection_a_chunk", "fp8_kv",
        "fp8_index"}


def test_reference_judges_the_streams_of_a_length_together():
    """One stream whose every token carries one error (a greedy stream on
    seeded weights settles on one token) does not read the limit for the
    other three; the same error on every stream does."""
    quiet = {"logprob_err": np.full(32, 0.002), "argmax_gap": np.zeros(32)}
    stuck = {"logprob_err": np.full(32, 0.02), "argmax_gap": np.zeros(32)}
    assert REF.REFERENCE_STREAMS == 4
    assert REF.summary([stuck])["logprob_err"] > REF.TOLERANCE_DENSE
    got = REF.summary([stuck, quiet, quiet, quiet])
    assert got["logprob_err"] == pytest.approx(0.002)
    assert got["logprob_err_max"] == pytest.approx(0.02)
    assert REF.summary([stuck] * 4)["logprob_err"] > REF.TOLERANCE_DENSE
    # a quartile: the worst three quarters of the tokens judge nothing
    off = {"logprob_err": np.zeros(32), "argmax_gap": np.full(32, 1.0)}
    right = {"logprob_err": np.zeros(32), "argmax_gap": np.zeros(32)}
    assert REF.summary([off, right, right, right])["argmax_gap"] == 0
    assert REF.summary([off, off, off, off])["argmax_gap"] == 1.0


def test_reference_imports_nothing_from_the_program():
    src = open(os.path.join(spec.PERF_DIR, "reference", "keye_vl2.py")).read()
    assert "import tpulab" not in src and "from tpulab" not in src


# ------------------------------------------------------- the roofline ----

def test_parameter_counts_are_the_issues():
    assert ROOFLINE.attention_params(KEYE) == 18_874_368          # 18.87 M
    assert ROOFLINE.indexer_params(KEYE) == 2_260_992             # 2.26 M
    assert ROOFLINE.expert_params(KEYE) == 4_718_592              # 4.72 M
    assert ROOFLINE.layer_params(KEYE) == 625_377_280             # 625.4 M
    assert ROOFLINE.embedding_params(KEYE) == 622_329_856         # 622.3 M
    total = 6 * ROOFLINE.layer_params(KEYE) + ROOFLINE.embedding_params(KEYE)
    assert 4_374.5e6 < total < 4_374.8e6                          # 8.75 GB


def test_decode_step_bytes_counts_weights_index_keys_and_selected_rows():
    none = ROOFLINE.decode_step_bytes(KEYE, 0, 0, 0)
    assert none == 2 * (6 * (18_874_368 + 2_260_992 + 2048 * 128)
                        + 2048 * 151_936)
    every = ROOFLINE.decode_step_bytes(KEYE, 0, 0, 128)
    assert every == 2 * (6 * 625_377_280 + 622_329_856 // 2)
    # 8 lanes at 10 k keys: 64 index values a key, 2,048 selected K and V rows
    at10k = ROOFLINE.decode_step_bytes(KEYE, 8, 10_000, 52)
    assert at10k - ROOFLINE.decode_step_bytes(KEYE, 0, 0, 52) == (
        2 * 6 * 8 * (10_000 * 64 + 2048 * 1024))
    # under topk every key is read
    assert (ROOFLINE.decode_step_bytes(KEYE, 1, 100, 0) - none
            == 2 * 6 * 100 * (64 + 1024))
    assert 3.7e9 < at10k < 4.1e9          # the issue's ~3.8 GB + 0.26 GB


def test_kernel_costs_from_shapes():
    one = ROOFLINE.index_scores_cost(KEYE, 1, 10_000)
    assert one["flops"] == 10_000 * 16 * (2 * 64 + 3)
    assert one["bytes"] == 2 * (10_000 * 64 + 16 * 64) + 4 * (16 + 10_000)
    chunk = ROOFLINE.index_scores_cost(KEYE, 256, 10_000)
    assert chunk["flops"] == 256 * one["flops"]
    att = ROOFLINE.sparse_attention_cost(KEYE, 1, 10_000)
    assert att["flops"] == 4 * 2048 * 32 * 128
    assert att["bytes"] == 2 * (2048 * 1024 + 2 * 32 * 128)
    assert ROOFLINE.sparse_attention_cost(KEYE, 1, 100)["flops"] == (
        4 * 100 * 32 * 128)


# -------------------------------------------------------- the readers ----

class _Cell:
    config = KEYE

    @staticmethod
    def module(kind, name):
        return spec.load_module(kind, name)


def _sparse(rows, scored, attended):
    return {"topk": 2048,
            "query_rows": {"decode": rows, "round": 2 * rows},
            "keys_scored": {"decode": scored, "round": 3 * scored},
            "keys_attended": {"decode": attended, "round": attended},
            "dense_rows": {"decode": 0, "round": 0}}


def _ctx(trace=None):
    moe = lambda steps: {"expert_layers": list(range(6)),
                         "assignments": [[1] * 128] * 6,
                         "decode_steps": steps,
                         "experts_hit": steps * 6 * 50}
    pool = {"n_pages": 12289, "page_size": 16,
            "hbm_bytes": 12289 * 16 * 13824, "index_bytes_per_token": 1536}
    return {"cell": _Cell, "trace": trace,
            "gauges": [{"active_lanes": 8}, {"active_lanes": 6}],
            "counters_before": {"moe": moe(10), "pool": pool,
                                "sparse": _sparse(100, 500_000, 100_000)},
            "counters_after": {"moe": moe(110), "pool": pool,
                               "sparse": _sparse(700, 6_500_000, 1_300_000)}}


def test_new_readers_on_a_canned_context():
    read = lambda name, ctx: spec.load_module("layer_metrics", name).read(ctx)
    ctx = _ctx()
    assert read("dsa.index_bytes_per_token", ctx) == 1536
    # (1.2 M + 1.2 M) attended of (6 M + 18 M) scored
    assert read("dsa.attended_share", ctx) == pytest.approx(10.0)
    assert read("dsa.decode_roofline", ctx) is None            # no trace
    # a program without the counters (the parent), or a model without an
    # indexer: nothing to read, no error
    for old in ({"dispatch": {}}, {"dispatch": {}, "pool": {
            "n_pages": 9, "page_size": 16, "hbm_bytes": 1}}):
        bare = {"cell": _Cell, "trace": {"modules": {}},
                "counters_before": old, "counters_after": old}
        for name in ("dsa.index_bytes_per_token", "dsa.attended_share",
                     "dsa.decode_roofline"):
            assert read(name, bare) is None


def test_decode_roofline_is_bytes_over_bandwidth_over_mean_step(monkeypatch):
    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    trace = {"modules": {
        "jit_paged_decode_block_k2": {"durations_s": [0.020, 0.024]},
        "jit_paged_decode_block_k1": {"durations_s": [0.016]},
        "jit_paged_mixed_step": {"durations_s": [0.5]}}}
    ctx = _ctx(trace)
    lanes = spec.load_module("layer_metrics",
                             "sched.active_lanes_mean").read(ctx)
    got = spec.load_module("layer_metrics", "dsa.decode_roofline").read(ctx)
    mean_step = (0.020 + 0.024 + 0.016) / (2 + 2 + 1)
    want = 100 * ROOFLINE.decode_step_bytes(
        KEYE, lanes, 6_000_000 / 600, 50.0) / 819e9 / mean_step
    assert got == pytest.approx(want) and 0 < got < 100


# ------------------------------------------------------ the cell's files ----

def test_the_new_cell_resolves():
    cell = spec.load_cell("keyevl2-l6.longdoc")
    assert cell.chips == 1 and cell.config["kind"] == "keye_vl2"
    assert cell.config["num_hidden_layers"] == 6
    assert cell.config["reduced"].keys() == {"num_hidden_layers"}
    assert cell.traffic["engine"] == {"lanes": 8, "max_len": 32768,
                                      "page_size": 16, "pool_tokens": 196608}
    assert cell.traffic["reference_prompt_lens"] == [24, 5000]
    names = {m["name"] for m in cell.per_layer}
    assert {"dsa.index_bytes_per_token", "dsa.attended_share",
            "dsa.decode_roofline", "moe.experts_hit_per_step",
            "kv.bytes_per_token"} <= names
    assert "step.decode_weight_roofline" not in names
    for m in cell.per_layer:
        assert callable(cell.module("layer_metrics", m["name"]).read)
    for kind in ("models", "reference", "rooflines"):
        cell.module(kind, "keye_vl2")
    from harness.sizes import size_pairs
    pairs = size_pairs(cell.traffic, 8)
    assert pairs.sum(0).tolist() == [72777, 8509]
    assert pairs[:, 0].min() == 3804 and pairs[:, 0].max() == 17641


def test_the_catalog_rows_keys_are_in_the_file_as_published():
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 151936,
        "intermediate_size": 6144, "moe_intermediate_size": 768,
        "num_experts": 128, "num_experts_per_tok": 8, "norm_topk_prob": True,
        "rope_theta": 10000000, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 262144, "tie_word_embeddings": False}
    for key, value in published.items():
        assert KEYE[key] == value, key
    assert KEYE["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert {"assumed", "departures", "stands_for", "layout"} <= set(KEYE)


# ------------------------------------------------ the overlay cell, CPU ----

def test_tiny_keye_cell_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.PERF_DIR, "run.py"), "--workload",
         "tiny-keye.closed", "--seed", str(2**31 + 13), "--seconds", "2",
         "--trace", "1", "--benchmark", CELLS, "--allow-cpu"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] and out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    m = out["metrics"]
    assert "entry=kv_index" in proc.stdout and "ragged=True" in proc.stdout
    # bf16: K and V of 2 heads x 32 and an index row padded to 128, 2 layers
    assert m["kv.bytes_per_token"]["value"] == 2 * (2 * 64 + 128) * 2
    assert m["dsa.index_bytes_per_token"]["value"] == 2 * 128 * 2
    # prompts of 40-90 tokens against topk 32: most keys are dropped
    assert 20 < m["dsa.attended_share"]["value"] < 90
    assert 1 <= m["moe.experts_hit_per_step"]["value"] <= 8
    assert "dsa.decode_roofline" not in m             # no TPU trace on a CPU
