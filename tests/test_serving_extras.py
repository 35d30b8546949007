"""Tests: server-side dynamic batching, metrics, torch weight import."""

import time

import numpy as np
import pytest

import tpulab
from tpulab.engine import InferenceManager
from tpulab.engine.batched_runner import BatchedInferRunner
from tpulab.models.mnist import make_mnist


# ----------------------------------------------------------- batched runner --
@pytest.fixture(scope="module")
def mgr():
    m = InferenceManager(max_executions=2, max_buffers=8)
    m.register_model("mnist", make_mnist(max_batch_size=8))
    m.update_resources()
    yield m
    m.shutdown()


def test_batched_runner_aggregates(mgr):
    runner = BatchedInferRunner(mgr, "mnist", window_s=0.05)
    try:
        x = np.random.default_rng(0).standard_normal((1, 28, 28, 1)).astype(np.float32)
        futs = [runner.infer(Input3=x) for _ in range(8)]  # closes by size
        outs = [f.result(timeout=60) for f in futs]
        assert all(o["Plus214_Output_0"].shape == (1, 10) for o in outs)
        # every caller gets identical rows for identical inputs
        for o in outs[1:]:
            np.testing.assert_allclose(o["Plus214_Output_0"],
                                       outs[0]["Plus214_Output_0"], rtol=1e-5)
    finally:
        runner.shutdown()


def test_batched_runner_matches_unbatched(mgr):
    """Numerics: batched path == direct path per request."""
    runner = BatchedInferRunner(mgr, "mnist", window_s=0.02)
    try:
        rng = np.random.default_rng(1)
        xs = [rng.standard_normal((1, 28, 28, 1)).astype(np.float32)
              for _ in range(4)]
        futs = [runner.infer(Input3=x) for x in xs]
        batched = [f.result(timeout=60) for f in futs]
        for x, out in zip(xs, batched):
            direct = mgr.infer_runner("mnist").infer(Input3=x).result(timeout=60)
            np.testing.assert_allclose(out["Plus214_Output_0"],
                                       direct["Plus214_Output_0"],
                                       rtol=1e-4, atol=1e-5)
    finally:
        runner.shutdown()


def test_batched_runner_window_timeout(mgr):
    runner = BatchedInferRunner(mgr, "mnist", window_s=0.02)
    try:
        x = np.zeros((1, 28, 28, 1), np.float32)
        out = runner.infer(Input3=x).result(timeout=30)  # lone request
        assert out["Plus214_Output_0"].shape == (1, 10)
    finally:
        runner.shutdown()


def test_batched_runner_mixed_batch_sizes(mgr):
    runner = BatchedInferRunner(mgr, "mnist", window_s=0.03)
    try:
        f1 = runner.infer(Input3=np.ones((3, 28, 28, 1), np.float32))
        f2 = runner.infer(Input3=np.ones((2, 28, 28, 1), np.float32))
        o1, o2 = f1.result(timeout=30), f2.result(timeout=30)
        assert o1["Plus214_Output_0"].shape == (3, 10)
        assert o2["Plus214_Output_0"].shape == (2, 10)
    finally:
        runner.shutdown()


def test_batched_runner_overflow_flushes(mgr):
    """A request that would overflow the open batch flushes it first."""
    runner = BatchedInferRunner(mgr, "mnist", window_s=5.0)  # long window
    try:
        f1 = runner.infer(Input3=np.ones((5, 28, 28, 1), np.float32))
        f2 = runner.infer(Input3=np.ones((6, 28, 28, 1), np.float32))
        # f1's group was flushed by f2's arrival despite the long window
        assert f1.result(timeout=30)["Plus214_Output_0"].shape == (5, 10)
        runner.flush()
        assert f2.result(timeout=30)["Plus214_Output_0"].shape == (6, 10)
    finally:
        runner.shutdown()


# -------------------------------------------------------- batching service --
def test_serve_with_batching_enabled():
    mgr = tpulab.InferenceManager(max_exec_concurrency=2)
    mgr.register_model("mnist", make_mnist(max_batch_size=8))
    mgr.update_resources()
    mgr.serve(port=0, batching=True, batch_window_s=0.02)
    from tpulab.rpc.infer_service import RemoteInferenceManager
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        runner = remote.infer_runner("mnist")
        x = np.zeros((1, 28, 28, 1), np.float32)
        futs = [runner.infer(Input3=x) for _ in range(12)]
        outs = [f.result(timeout=60) for f in futs]
        assert all(o["Plus214_Output_0"].shape == (1, 10) for o in outs)
        # the serving stage profile accumulated per-request costs: total
        # covers its parts, and the batch window shows up as batch_wait
        prof = mgr.server._infer_resources.stage_profile()
        assert prof["n"] == 12
        for key in ("handler_total_ms", "batch_wait_ms", "pipeline_ms",
                    "compute_ms", "respond_ms"):
            assert key in prof, prof
        assert prof["handler_total_ms"] >= prof["respond_ms"]
        assert prof["batch_wait_ms"] >= 0.0
    finally:
        remote.close()
        mgr.shutdown()


# ------------------------------------------------------------------ metrics --
def test_inference_metrics_observations():
    from tpulab.utils.metrics import InferenceMetrics, LOAD_RATIO_BUCKETS
    m = InferenceMetrics(namespace="test")
    for i in range(50):
        m.observe_request(request_s=0.010 + i * 1e-4, compute_s=0.008)
    from prometheus_client import generate_latest
    text = generate_latest(m.registry).decode()
    assert "test_request_total 50.0" in text
    assert 'test_request_duration_seconds{quantile="0.5"}' in text
    assert "test_load_ratio_bucket" in text
    m.inc_queue_depth(); m.dec_queue_depth()
    m.poll_device()  # no HBM stats on CPU — must not raise


def test_metrics_wired_into_service():
    from tpulab.utils.metrics import InferenceMetrics
    from prometheus_client import generate_latest
    metrics = InferenceMetrics(namespace="svc")
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=2))
    mgr.update_resources()
    mgr.serve(port=0, metrics=metrics)
    from tpulab.rpc.infer_service import RemoteInferenceManager
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        runner = remote.infer_runner("mnist")
        runner.infer(Input3=np.zeros((1, 28, 28, 1), np.float32)).result(timeout=30)
        text = generate_latest(metrics.registry).decode()
        assert "svc_request_total 1.0" in text
    finally:
        remote.close()
        mgr.shutdown()


# --------------------------------------------------------------- torch zoo --
def test_torch_resnet_import_roundtrip():
    """Build a torch-style ResNet50 state_dict and import it; BN must fold
    exactly (conv+BN == conv*scale+bias)."""
    torch = pytest.importorskip("torch")
    import torch.nn as nn

    # minimal torchvision-layout resnet50 state_dict (random weights)
    sd = {}
    rng = np.random.default_rng(0)

    def add_conv_bn(prefix_c, prefix_b, cout, cin, k):
        sd[f"{prefix_c}.weight"] = torch.tensor(
            rng.standard_normal((cout, cin, k, k)).astype(np.float32) * 0.05)
        sd[f"{prefix_b}.weight"] = torch.tensor(
            1 + rng.standard_normal(cout).astype(np.float32) * 0.1)
        sd[f"{prefix_b}.bias"] = torch.tensor(
            rng.standard_normal(cout).astype(np.float32) * 0.1)
        sd[f"{prefix_b}.running_mean"] = torch.tensor(
            rng.standard_normal(cout).astype(np.float32) * 0.1)
        sd[f"{prefix_b}.running_var"] = torch.tensor(
            np.abs(1 + rng.standard_normal(cout).astype(np.float32) * 0.1))

    add_conv_bn("conv1", "bn1", 64, 3, 7)
    cin = 64
    for stage, blocks in enumerate([3, 4, 6, 3]):
        cmid = 64 * 2 ** stage
        cout = cmid * 4
        for b in range(blocks):
            pre = f"layer{stage + 1}.{b}"
            add_conv_bn(f"{pre}.conv1", f"{pre}.bn1", cmid, cin, 1)
            add_conv_bn(f"{pre}.conv2", f"{pre}.bn2", cmid, cmid, 3)
            add_conv_bn(f"{pre}.conv3", f"{pre}.bn3", cout, cmid, 1)
            if b == 0:
                add_conv_bn(f"{pre}.downsample.0", f"{pre}.downsample.1",
                            cout, cin, 1)
            cin = cout
    sd["fc.weight"] = torch.tensor(
        rng.standard_normal((1000, 2048)).astype(np.float32) * 0.01)
    sd["fc.bias"] = torch.tensor(np.zeros(1000, np.float32))

    from tpulab.models.torch_import import make_resnet_from_torch
    import jax.numpy as jnp
    model = make_resnet_from_torch(sd, depth=50, max_batch_size=1,
                                   compute_dtype=jnp.float32)
    assert model.params["stem"]["kernel"].shape == (7, 7, 3, 64)
    assert "proj" in model.params["s0b0"] and "proj" not in model.params["s0b1"]
    # forward runs and is finite
    x = {"input": np.zeros((1, 224, 224, 3), np.float32)}
    out = model.apply_fn(model.params, x)["logits"]
    assert np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------- generation --
def test_generation_engine_sessions():
    import jax.numpy as jnp
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.transformer import init_transformer_params

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    eng = GenerationEngine(params, n_heads=2, n_layers=2, max_len=48,
                           max_sessions=2, compute_dtype=jnp.float32)
    prompt = np.random.default_rng(0).integers(0, 64, (8,), np.int32)

    # streaming session matches one-shot jitted generate
    with eng.start_session() as s:
        s.prefill(prompt)
        streamed = list(s.stream(6))
    batch = eng.generate(prompt[None, :], 6)[0]
    np.testing.assert_array_equal(np.asarray(streamed), batch)

    # slots recycle and start clean
    assert eng.available_sessions == 2
    with eng.start_session() as s2:
        s2.prefill(prompt)
        again = list(s2.stream(6))
    np.testing.assert_array_equal(np.asarray(again), batch)


def test_generation_engine_gqa_sessions():
    """GQA params through the dense session API: compact caches, streaming
    session == one-shot generate, == the paged batcher's tokens."""
    import jax.numpy as jnp
    from tpulab.engine.generation import GenerationEngine
    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params

    params = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=64, n_kv_heads=2)
    eng = GenerationEngine(params, n_heads=4, n_layers=2, max_len=48,
                           max_sessions=1, compute_dtype=jnp.float32,
                           n_kv_heads=2)
    prompt = np.random.default_rng(1).integers(0, 64, (6,), np.int32)
    with eng.start_session() as s:
        s.prefill(prompt)
        streamed = list(s.stream(5))
    batch = eng.generate(prompt[None, :], 5)[0]
    np.testing.assert_array_equal(np.asarray(streamed), batch)

    cb = ContinuousBatcher(params, n_heads=4, n_layers=2, lanes=1,
                           max_len=48, page_size=8,
                           compute_dtype=jnp.float32, n_kv_heads=2)
    try:
        paged = cb.submit(prompt, 5).result(timeout=120)
        np.testing.assert_array_equal(np.asarray(paged), batch)
    finally:
        cb.shutdown()


def test_generation_session_backpressure_and_limits():
    import jax.numpy as jnp
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.transformer import init_transformer_params

    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    eng = GenerationEngine(params, n_heads=2, n_layers=1, max_len=8,
                           max_sessions=1, compute_dtype=jnp.float32)
    s = eng.start_session()
    with pytest.raises(TimeoutError):
        eng.start_session(timeout=0.05)   # pool exhausted — backpressure
    with pytest.raises(ValueError, match="max_len"):
        s.prefill(np.zeros(9, np.int32))  # over capacity
    s.prefill(np.zeros(4, np.int32))
    s.close()
    s.close()  # idempotent
    s2 = eng.start_session(timeout=1)
    s2.close()


def test_generate_rpc_streams_tokens():
    """End-to-end: Generate RPC streams tokens matching local generation."""
    import jax.numpy as jnp
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    eng = GenerationEngine(params, n_heads=2, n_layers=2, max_len=32,
                           max_sessions=2, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))  # any model
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": eng})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        prompt = np.random.default_rng(0).integers(0, 64, (6,), np.int32)
        client = GenerateStreamClient(remote, "lm")
        streamed = list(client.generate(prompt, 5))
        local = eng.generate(prompt[None, :], 5)[0]
        np.testing.assert_array_equal(np.asarray(streamed), local)
        # unknown generation model -> clean error
        with pytest.raises(RuntimeError, match="no generation engine"):
            list(GenerateStreamClient(remote, "nope").generate(prompt, 2))
    finally:
        remote.close()
        mgr.shutdown()


def test_generate_rpc_under_fiber_executor():
    """Generation under the aio executor must not stall other RPCs."""
    import jax.numpy as jnp
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.executor import FiberExecutor
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    eng = GenerationEngine(params, n_heads=2, n_layers=1, max_len=32,
                           max_sessions=1, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, executor=FiberExecutor(), generation_engines={"lm": eng})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        import threading
        prompt = np.zeros(4, np.int32)
        toks = []
        t = threading.Thread(target=lambda: toks.extend(
            GenerateStreamClient(remote, "lm").generate(prompt, 10)))
        t.start()
        # unary traffic stays live while generation streams
        assert "mnist" in remote.get_models()
        t.join(timeout=120)
        assert len(toks) == 10
    finally:
        remote.close()
        mgr.shutdown()


def test_generation_session_use_after_close():
    import jax.numpy as jnp
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    eng = GenerationEngine(params, n_heads=2, n_layers=1, max_len=8,
                           max_sessions=1, compute_dtype=jnp.float32)
    s = eng.start_session()
    s.prefill(np.zeros(2, np.int32))
    s.close()
    with pytest.raises(RuntimeError, match="closed"):
        s.step()
    with pytest.raises(RuntimeError, match="closed"):
        s.prefill(np.zeros(1, np.int32))


# ---------------------------------------------------------------- watchdog --
def test_watchdog_healthy_and_wedge_detection():
    from tpulab.utils.watchdog import DeviceWatchdog
    events = []
    wd = DeviceWatchdog(period_s=0.05, deadline_s=5.0,
                        on_unhealthy=events.append).start()
    try:
        time.sleep(0.4)
        assert wd.healthy and wd.seconds_since_ok is not None
        # wedge simulation: canary that never completes
        import threading
        wd._canary = (lambda x: _Never(), wd._canary[1])
        wd.deadline_s = 0.1
        time.sleep(0.5)
        assert not wd.healthy
        assert "deadline" in wd.reason or "outstanding" in wd.reason
        assert events  # hook fired
    finally:
        wd.stop()


class _Never:
    def block_until_ready(self):
        time.sleep(60)


def test_watchdog_wired_into_health_rpc():
    """Unhealthy watchdog -> Health RPC reports not-ready (review finding)."""
    from tpulab.rpc.client import ClientExecutor, ClientUnary
    from tpulab.rpc.infer_service import SERVICE_NAME
    from tpulab.rpc.protos import inference_pb2 as pb

    class FakeWatchdog:
        healthy = True

    wd = FakeWatchdog()
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, watchdog=wd)
    try:
        with ClientExecutor(f"localhost:{mgr.server.bound_port}") as cx:
            health = ClientUnary(cx, f"/{SERVICE_NAME}/Health",
                                 pb.HealthRequest.SerializeToString,
                                 pb.HealthResponse.FromString)
            assert health.call(pb.HealthRequest(), timeout=30).ready
            wd.healthy = False
            resp = health.call(pb.HealthRequest(), timeout=30)
            assert resp.live and not resp.ready
    finally:
        mgr.shutdown()


def test_stream_infer_with_batching_enabled():
    """Regression: StreamInfer handlers block on batch futures — the batched
    runner's window launches must not share their worker pool (deadlock)."""
    from tpulab.rpc.infer_service import (RemoteInferenceManager,
                                          StreamInferClient)
    mgr = tpulab.InferenceManager(max_exec_concurrency=2)
    mgr.register_model("mnist", make_mnist(max_batch_size=8))
    mgr.update_resources()
    mgr.serve(port=0, batching=True, batch_window_s=0.01)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    client = StreamInferClient(remote, "mnist")
    try:
        x = np.zeros((1, 28, 28, 1), np.float32)
        futs = [client.submit(Input3=x) for _ in range(8)]
        outs = [f.result(timeout=60) for f in futs]
        assert all(o["Plus214_Output_0"].shape == (1, 10) for o in outs)
    finally:
        client.close()
        remote.close()
        mgr.shutdown()


# ------------------------------------------------------------ llama family --
def test_rope_matches_complex_rotation():
    """apply_rope == the textbook complex-plane rotation at each position."""
    import jax.numpy as jnp

    from tpulab.models.transformer import apply_rope

    rng = np.random.default_rng(0)
    b, t, h, d = 2, 5, 3, 8
    x = rng.standard_normal((b, t, h, d)).astype(np.float32)
    theta = 10000.0
    got = np.asarray(apply_rope(jnp.asarray(x), jnp.arange(t), theta))
    # reference: pair (x[i], x[i+d/2]) as a complex number, rotate by
    # pos * theta^(-2i/d) (the HF rotate-half convention)
    half = d // 2
    inv = 1.0 / theta ** (np.arange(half) / half)
    ang = np.arange(t)[:, None] * inv[None, :]            # (T, half)
    z = x[..., :half] + 1j * x[..., half:]
    zr = z * np.exp(1j * ang)[None, :, None, :]
    want = np.concatenate([zr.real, zr.imag], axis=-1).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_llama_family_paged_matches_dense():
    """RoPE + SwiGLU + GQA + untied head end to end: the paged batcher
    reproduces the dense KV-cache decode exactly."""
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import (init_transformer_params,
                                           make_generate_fn)

    params = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=96, n_kv_heads=2,
                                     ffn="swiglu", tie_embeddings=False)
    kw = dict(n_kv_heads=2, rope_theta=10000.0)
    dense = make_generate_fn(params, n_heads=4, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32, **kw)
    cb = ContinuousBatcher(params, n_heads=4, n_layers=2, lanes=2,
                           max_len=64, page_size=8,
                           compute_dtype=jnp.float32, **kw)
    try:
        for s in range(2):
            p = np.random.default_rng(s).integers(0, 64, (5 + s,), np.int32)
            got = cb.submit(p, 6).result(timeout=120)
            want = np.asarray(dense(p[None, :], 6)[0])
            np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()


def test_llama_torch_import_roundtrip():
    """A synthetic HF-Llama state_dict imports into the transformer family
    and serves: wqkv fuses q/k/v correctly (checked against a manual
    forward of the q slice) and dense == paged generation."""
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.torch_import import llama_params_from_torch
    from tpulab.models.transformer import make_generate_fn

    rng = np.random.default_rng(3)
    vocab, dm, hq, hkv, dff, nl = 64, 64, 4, 2, 96, 2
    hd = dm // hq

    def lin(o, i):
        return rng.standard_normal((o, i)).astype(np.float32) * 0.05

    sd = {"model.embed_tokens.weight": lin(vocab, dm),
          "model.norm.weight": np.ones((dm,), np.float32),
          "lm_head.weight": lin(vocab, dm)}
    for i in range(nl):
        pre = f"model.layers.{i}"
        sd.update({
            f"{pre}.input_layernorm.weight": np.ones((dm,), np.float32),
            f"{pre}.post_attention_layernorm.weight":
                np.ones((dm,), np.float32),
            f"{pre}.self_attn.q_proj.weight": lin(hq * hd, dm),
            f"{pre}.self_attn.k_proj.weight": lin(hkv * hd, dm),
            f"{pre}.self_attn.v_proj.weight": lin(hkv * hd, dm),
            f"{pre}.self_attn.o_proj.weight": lin(dm, dm),
            f"{pre}.mlp.gate_proj.weight": lin(dff, dm),
            f"{pre}.mlp.up_proj.weight": lin(dff, dm),
            f"{pre}.mlp.down_proj.weight": lin(dm, dff),
        })
    params = llama_params_from_torch(sd, n_layers=nl)
    # fusion layout: wqkv's q columns must be q_proj.T
    np.testing.assert_array_equal(
        np.asarray(params["layer0"]["wqkv"][:, :hq * hd]),
        sd["model.layers.0.self_attn.q_proj.weight"].T)
    assert "w3" in params["layer0"] and "lm_head" in params

    kw = dict(n_kv_heads=hkv, rope_theta=10000.0)
    dense = make_generate_fn(params, n_heads=hq, n_layers=nl, max_len=48,
                             compute_dtype=jnp.float32, **kw)
    cb = ContinuousBatcher(params, n_heads=hq, n_layers=nl, lanes=1,
                           max_len=48, page_size=8,
                           compute_dtype=jnp.float32, **kw)
    try:
        p = np.asarray([5, 9, 2, 41], np.int32)
        got = cb.submit(p, 6).result(timeout=120)
        want = np.asarray(dense(p[None, :], 6)[0])
        np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()


# ------------------------------------------------------- speculative decode --
def test_speculative_equals_target_greedy():
    """Speculative decoding is latency-only: output == the target model's
    vanilla greedy sequence, for a perfect draft (the target itself, full
    acceptance) AND a mismatched draft (low acceptance)."""
    import jax.numpy as jnp

    from tpulab.engine.speculative import SpeculativeGenerator
    from tpulab.models.transformer import (init_transformer_params,
                                           make_generate_fn)

    kw = dict(n_kv_heads=2, rope_theta=10000.0)
    target = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=96, n_kv_heads=2,
                                     ffn="swiglu", seed=0)
    draft = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                    n_layers=1, d_ff=48, n_kv_heads=2,
                                    ffn="swiglu", seed=9)
    dense = make_generate_fn(target, n_heads=4, n_layers=2, max_len=96,
                             compute_dtype=jnp.float32, **kw)
    prompt = np.random.default_rng(0).integers(0, 64, (6,), np.int32)
    steps = 12
    want = list(np.asarray(dense(prompt[None, :], steps)[0]))

    # perfect draft: every proposal accepted -> k tokens per round + bonus
    g_self = SpeculativeGenerator(
        target, target, n_heads=4, n_layers=2, k=3, max_len=96,
        compute_dtype=jnp.float32, **kw)
    got = g_self.generate(prompt, steps)
    assert got == want, (got, want)
    assert g_self.accepted == g_self.rounds * 3  # full acceptance

    # same invariant at realistic weight scale, where attention strongly
    # discriminates positions: a hole in the draft KV cache (e.g. the last
    # accepted proposal never fed back) breaks full acceptance here even
    # though init-scale weights would mask it
    import jax
    big = jax.tree_util.tree_map(lambda x: x * 8.0, target)
    g_big = SpeculativeGenerator(
        big, big, n_heads=4, n_layers=2, k=3, max_len=96,
        compute_dtype=jnp.float32, **kw)
    g_big.generate(prompt, steps)
    assert g_big.accepted == g_big.rounds * 3, \
        (g_big.accepted, g_big.rounds)

    # mismatched draft (different arch + seed): still exactly greedy
    g_mix = SpeculativeGenerator(
        target, draft, n_heads=4, n_layers=2, draft_n_heads=2,
        draft_n_layers=1, draft_n_kv_heads=2, k=3, max_len=96,
        compute_dtype=jnp.float32, **kw)
    got2 = g_mix.generate(prompt, steps)
    assert got2 == want, (got2, want)
    assert g_mix.rounds >= g_self.rounds  # worse draft -> more rounds


def test_engines_reject_out_of_range_ids_at_library_boundary():
    """ADVICE r5: XLA gather CLAMPS out-of-bounds token ids (silent
    garbage).  ContinuousBatcher.submit always validated; the dense and
    speculative engines must reject DIRECT library callers too, not just
    the Generate RPC's shared check."""
    import jax.numpy as jnp
    import pytest

    from tpulab.engine.generation import GenerationEngine
    from tpulab.engine.speculative import SpeculativeGenerator
    from tpulab.models.transformer import init_transformer_params

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=48)
    eng = GenerationEngine(params, n_heads=2, n_layers=1, max_len=32,
                           max_sessions=1, compute_dtype=jnp.float32)
    bad = np.array([3, 64], np.int32)          # 64 == vocab: one past
    with pytest.raises(ValueError, match=r"outside \[0, 64\)"):
        eng.generate(bad[None, :], 2)
    with eng.start_session() as sess:
        with pytest.raises(ValueError, match=r"outside \[0, 64\)"):
            sess.prefill(np.array([-1, 3], np.int32))
        sess.prefill(np.array([1, 2], np.int32))   # session still usable
        with pytest.raises(ValueError, match=r"outside \[0, 64\)"):
            sess.step(64)                          # teacher-forced id too
        assert 0 <= sess.step() < 64

    spec = SpeculativeGenerator(params, params, n_heads=2, n_layers=1,
                                k=2, max_len=32, compute_dtype=jnp.float32)
    with pytest.raises(ValueError, match=r"outside \[0, 64\)"):
        spec.stream(np.array([0, 64], np.int32), 2)  # EAGER: at call time


def test_speculative_served_through_generate_rpc():
    """SpeculativeSessionEngine plugs speculation into the serving path:
    tokens stream over the Generate RPC in verified bursts and equal the
    target model's vanilla greedy sequence; sampling is rejected (the
    dense-path greedy-only contract)."""
    import jax.numpy as jnp

    from tpulab.engine.speculative import (SpeculativeGenerator,
                                           SpeculativeSessionEngine)
    from tpulab.models.transformer import (init_transformer_params,
                                           make_generate_fn)
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          GenerationRejected,
                                          RemoteInferenceManager)

    kw = dict(n_kv_heads=2, rope_theta=10000.0)
    target = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=96, n_kv_heads=2,
                                     ffn="swiglu", seed=0)
    dense = make_generate_fn(target, n_heads=4, n_layers=2, max_len=96,
                             compute_dtype=jnp.float32, **kw)
    spec = SpeculativeGenerator(target, target, n_heads=4, n_layers=2,
                                k=3, max_len=96, compute_dtype=jnp.float32,
                                **kw)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={
        "lm-spec": SpeculativeSessionEngine(spec, max_sessions=1)})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        prompt = np.random.default_rng(0).integers(0, 64, (6,), np.int32)
        steps = 12
        want = list(np.asarray(dense(prompt[None, :], steps)[0]))
        client = GenerateStreamClient(remote, "lm-spec")
        got = list(client.generate(prompt, steps))
        assert got == want, (got, want)
        assert spec.rounds > 0 and spec.accepted == spec.rounds * 3
        with pytest.raises(GenerationRejected, match="dense session"):
            list(client.generate(prompt, 4, temperature=0.7))
    finally:
        remote.close()
        mgr.shutdown()


def test_speculative_session_contract():
    """Session shape parity with the dense engine: direct use + close(),
    context-manager use, admission release on both, use-after-close
    rejection, and the exactly-steps contract at steps=0."""
    import jax.numpy as jnp

    from tpulab.engine.speculative import (SpeculativeGenerator,
                                           SpeculativeSessionEngine)
    from tpulab.models.transformer import init_transformer_params

    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=48, seed=0)
    spec = SpeculativeGenerator(params, params, n_heads=2, n_layers=1,
                                k=2, max_len=64, compute_dtype=jnp.float32)
    assert spec.generate([1, 2, 3], 0) == []  # steps=0 -> no tokens
    eng = SpeculativeSessionEngine(spec, max_sessions=1)
    # direct (non-with) use must release the slot via close()
    s = eng.start_session(timeout=5)
    s.prefill([1, 2, 3])
    toks = list(s.stream(4))
    assert len(toks) == 4
    s.close()
    with pytest.raises(RuntimeError, match="closed"):
        s.prefill([1])
    # the slot is free again: context-manager use works immediately
    with eng.start_session(timeout=5) as s2:
        s2.prefill([1, 2, 3])
        assert list(s2.stream(4)) == toks  # deterministic greedy
    with eng.start_session(timeout=5):
        pass  # released by the with-exit above, not leaked


def test_speculative_completion_accounting():
    """completed_requests mirrors the batcher's success-only semantics:
    exhausted and stop-token-broken streams count; errored ones don't."""
    import jax.numpy as jnp

    from tpulab.engine.speculative import (SpeculativeGenerator,
                                           SpeculativeSessionEngine)
    from tpulab.models.transformer import init_transformer_params

    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=48, seed=0)
    spec = SpeculativeGenerator(params, params, n_heads=2, n_layers=1,
                                k=2, max_len=32, compute_dtype=jnp.float32)
    eng = SpeculativeSessionEngine(spec, max_sessions=1)
    # exhausted stream -> counts
    with eng.start_session(timeout=5) as s:
        s.prefill([1, 2, 3])
        assert len(list(s.stream(4))) == 4
    assert eng.completed_requests == 1
    # early break after served tokens (the stop-token path) -> counts
    with eng.start_session(timeout=5) as s:
        s.prefill([1, 2, 3])
        it = s.stream(6)
        next(it)
        it.close()
    assert eng.completed_requests == 2
    # error before any token (prompt+steps+k+1 > max_len) -> no count
    with eng.start_session(timeout=5) as s:
        s.prefill([1, 2, 3])
        with pytest.raises(ValueError, match="max_len"):
            next(s.stream(30))
    assert eng.completed_requests == 2


def test_top_p_over_generate_rpc():
    """top_p flows wire -> SamplingParams: with a seeded request the RPC
    stream equals local sampling with identical params, and
    device_sampling+top_p is rejected like top_k."""
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher, SamplingParams
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          GenerationRejected,
                                          RemoteInferenceManager)

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    cb = ContinuousBatcher(params, n_heads=2, n_layers=1, lanes=2,
                           max_len=64, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        prompt = np.arange(5, dtype=np.int32)
        client = GenerateStreamClient(remote, "lm")
        got = list(client.generate(prompt, 8, temperature=0.8, top_p=0.7,
                                   seed=11))
        want = list(cb.submit(
            prompt, 8, sampling=SamplingParams(
                temperature=0.8, top_p=0.7, seed=11)).result(timeout=120))
        assert got == want, (got, want)
        with pytest.raises(GenerationRejected, match="top_k/top_p"):
            list(client.generate(prompt, 4, temperature=0.8, top_p=0.7,
                                 device_sampling=True))
        with pytest.raises(GenerationRejected, match="top_p must be"):
            list(client.generate(prompt, 4, temperature=0.8, top_p=1.5))
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()
