"""End-to-end serving tests: local manager -> gRPC service -> remote client
(reference examples/30_PyTensorRT server.py/client.py + the Multiple Models
notebook flow, with golden numeric checks in the run_onnx_tests.py style)."""

import numpy as np
import pytest

import tpulab
from tpulab.models.mnist import make_mnist
from tpulab.rpc.infer_service import RemoteInferenceManager


@pytest.fixture(scope="module")
def serving():
    mgr = tpulab.InferenceManager(max_exec_concurrency=2)
    mgr.register_model("mnist", make_mnist(max_batch_size=4))
    mgr.update_resources()
    mgr.serve(port=0)  # ephemeral port
    port = mgr.server.bound_port
    remote = RemoteInferenceManager(f"localhost:{port}")
    yield mgr, remote
    remote.close()
    mgr.shutdown()


def test_remote_model_listing(serving):
    _mgr, remote = serving
    models = remote.get_models()
    assert "mnist" in models
    ms = models["mnist"]
    assert ms.max_batch_size == 4
    assert [i.name for i in ms.inputs] == ["Input3"]
    assert list(ms.batch_buckets) == [1, 2, 4]


def test_remote_infer_matches_local(serving):
    """Golden check: remote serving path == local pipeline numerically."""
    mgr, remote = serving
    x = np.random.default_rng(3).standard_normal((2, 28, 28, 1)).astype(np.float32)
    runner = remote.infer_runner("mnist")
    remote_out = runner.infer(Input3=x).result(timeout=60)
    local_out = mgr.infer_runner("mnist").infer(Input3=x).result(timeout=60)
    np.testing.assert_allclose(remote_out["Plus214_Output_0"],
                               local_out["Plus214_Output_0"], rtol=1e-5)


def test_remote_concurrent_requests(serving):
    _mgr, remote = serving
    runner = remote.infer_runner("mnist")
    x = np.zeros((1, 28, 28, 1), np.float32)
    futs = [runner.infer(Input3=x) for _ in range(16)]
    outs = [f.result(timeout=60) for f in futs]
    assert all(o["Plus214_Output_0"].shape == (1, 10) for o in outs)


def test_remote_request_larger_than_grpc_default_limit():
    """Tensors ride inside the messages: a full batch of images is far
    past gRPC's default 4 MiB per message (b=128 of 224x224x3 uint8 is
    19 MB), in both directions — server and channel lift the limit."""
    from tpulab.engine.model import IOSpec, Model
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("echo", Model(
        "echo", lambda p, x: {"out": x["img"]}, {},
        [IOSpec("img", (224, 224, 3), np.uint8)],
        [IOSpec("out", (224, 224, 3), np.uint8)],
        max_batch_size=32, batch_buckets=[32]))
    mgr.update_resources()
    mgr.serve(port=0, batching=True)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        x = np.random.default_rng(0).integers(
            0, 256, (32, 224, 224, 3)).astype(np.uint8)      # 4.8 MB
        out = remote.infer_runner("echo").infer(img=x).result(timeout=120)
        assert (out["out"] == x).all()
    finally:
        remote.close()
        mgr.shutdown()


def test_remote_unknown_model(serving):
    _mgr, remote = serving
    with pytest.raises(KeyError):
        remote.infer_runner("nope")


def test_remote_bad_dtype_is_clean_error(serving):
    _mgr, remote = serving
    runner = remote.infer_runner("mnist")
    bad = np.zeros((1, 28, 28, 1), np.float64)  # wrong dtype
    with pytest.raises(RuntimeError):
        runner.infer(Input3=bad).result(timeout=60)


def test_remote_requested_outputs_subset_and_unknown(serving):
    _mgr, remote = serving
    runner = remote.infer_runner("mnist")
    x = np.zeros((1, 28, 28, 1), np.float32)
    out = runner.infer(requested_outputs=["Plus214_Output_0"],
                       Input3=x).result(timeout=60)
    assert set(out) == {"Plus214_Output_0"}
    # a typo'd output name must be an INVALID_ARGUMENT error, not an
    # empty SUCCESS response
    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        runner.infer(requested_outputs=["Plus214_Output_0_typo"],
                     Input3=x).result(timeout=60)


def test_remote_binding_introspection(serving):
    _mgr, remote = serving
    runner = remote.infer_runner("mnist")
    assert runner.input_bindings()["Input3"][0] == (28, 28, 1)
    assert runner.output_bindings()["Plus214_Output_0"][1] == np.dtype(np.float32)


def test_stream_infer_pipelined(serving):
    """Bidirectional StreamInfer: N requests down one stream, correlated
    responses (reference TRTIS StreamInfer / streaming lifecycle)."""
    from tpulab.rpc.infer_service import StreamInferClient
    mgr, remote = serving
    client = StreamInferClient(remote, "mnist")
    try:
        x = np.random.default_rng(5).standard_normal((1, 28, 28, 1)).astype(np.float32)
        futs = [client.submit(Input3=x) for _ in range(8)]
        outs = [f.result(timeout=60) for f in futs]
        assert all(o["Plus214_Output_0"].shape == (1, 10) for o in outs)
        # parity with unary path
        unary = remote.infer_runner("mnist").infer(Input3=x).result(timeout=60)
        np.testing.assert_allclose(outs[0]["Plus214_Output_0"],
                                   unary["Plus214_Output_0"], rtol=1e-5)
    finally:
        client.close()


def test_stream_infer_bad_request_streams_error(serving):
    from tpulab.rpc.infer_service import StreamInferClient
    _mgr, remote = serving
    client = StreamInferClient(remote, "mnist")
    try:
        bad = np.zeros((1, 28, 28, 1), np.float64)
        with pytest.raises(RuntimeError):
            client.submit(Input3=bad).result(timeout=60)
        good = np.zeros((1, 28, 28, 1), np.float32)  # stream still healthy
        assert client.submit(Input3=good).result(timeout=60)[
            "Plus214_Output_0"].shape == (1, 10)
    finally:
        client.close()


def test_stream_infer_under_fiber_executor():
    """StreamInfer on the aio server: the async drain must not stall the
    loop (review finding) — concurrent Health calls stay live mid-stream."""
    from tpulab.rpc.executor import FiberExecutor
    from tpulab.rpc.infer_service import (RemoteInferenceManager,
                                          StreamInferClient)
    mgr = tpulab.InferenceManager(max_exec_concurrency=2)
    mgr.register_model("mnist", make_mnist(max_batch_size=2))
    mgr.update_resources()
    mgr.serve(port=0, executor=FiberExecutor())
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    client = StreamInferClient(remote, "mnist")
    try:
        x = np.zeros((1, 28, 28, 1), np.float32)
        futs = [client.submit(Input3=x) for _ in range(6)]
        # unary RPCs interleave with the open stream
        assert "mnist" in remote.get_models()
        outs = [f.result(timeout=60) for f in futs]
        assert all(o["Plus214_Output_0"].shape == (1, 10) for o in outs)
        client.close()
    finally:
        remote.close()
        mgr.shutdown()


def test_stream_infer_dead_stream_fails_pending():
    """Killing the server fails outstanding stream futures promptly."""
    from tpulab.rpc.infer_service import (RemoteInferenceManager,
                                          StreamInferClient)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    client = StreamInferClient(remote, "mnist")
    try:
        x = np.zeros((1, 28, 28, 1), np.float32)
        client.submit(Input3=x).result(timeout=60)  # stream established
        mgr.server.shutdown(grace_s=0.1)            # kill the server
        fut = client.submit(Input3=x)               # rides the dead stream
        with pytest.raises(Exception):
            fut.result(timeout=30)  # fails promptly, not via caller timeout
    finally:
        remote.close()
        mgr.shutdown()


def test_graceful_drain_flips_readiness_and_waits_for_inflight():
    """Rolling-restart drain: readiness false immediately (balancers
    rotate the replica out), requests in flight — and stragglers arriving
    during the drain window — still complete."""
    import threading
    import time

    import numpy as np

    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import RemoteInferenceManager

    mgr = tpulab.InferenceManager(max_exec_concurrency=2, max_buffers=4)
    mgr.register_model("mnist", make_mnist(max_batch_size=2))
    mgr.update_resources()
    mgr.serve(port=0)
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        runner = remote.infer_runner("mnist")
        x = np.zeros((1, 28, 28, 1), np.float32)
        runner.infer(Input3=x).result(timeout=60)  # warm
        assert remote.health().ready
        # keep a stream of requests going while the drain starts
        stop, results, errors = threading.Event(), [], []

        def pump():
            while not stop.is_set():
                try:
                    results.append(
                        runner.infer(Input3=x).result(timeout=60))
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                    return

        t = threading.Thread(target=pump)
        t.start()
        time.sleep(0.1)
        drained = mgr.drain(timeout=30.0, settle_s=0.3)
        health = remote.health()
        assert health.live and not health.ready  # rotated out, still alive
        # drain() returning True means in-flight hit zero at that moment;
        # the pump may still add stragglers — they must SUCCEED (drain
        # serves until shutdown, it never rejects)
        time.sleep(0.2)
        stop.set()
        t.join(timeout=60)
        assert not t.is_alive()
        assert drained
        assert not errors, errors
        assert len(results) >= 2
        assert all(o["Plus214_Output_0"].shape == (1, 10) for o in results)
    finally:
        remote.close()
        mgr.shutdown()


def test_drain_waits_for_generation_streams():
    """Generation streams count toward drain: an in-flight decode must
    hold drain() open (and finish intact) before shutdown proceeds."""
    import threading
    import time

    import jax.numpy as jnp
    import numpy as np

    import tpulab
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.mnist import make_mnist
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=48)
    eng = GenerationEngine(params, n_heads=2, n_layers=1, max_len=64,
                           max_sessions=1, compute_dtype=jnp.float32)

    class Paced:
        def start_session(self, timeout=None):
            import contextlib
            cm = eng.start_session(timeout=timeout)

            @contextlib.contextmanager
            def wrap():
                with cm as sess:
                    class S:
                        prefill = staticmethod(sess.prefill)

                        @staticmethod
                        def stream(steps):
                            for tok in sess.stream(steps):
                                time.sleep(0.03)
                                yield tok
                    yield S()
            return wrap()

    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": Paced()})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        toks, t_done = [], [None]

        def consume():
            toks.extend(GenerateStreamClient(remote, "lm").generate(
                np.arange(4, dtype=np.int32), 20))
            t_done[0] = time.monotonic()

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.2)  # stream is in flight
        t_drained = None
        drained = mgr.drain(timeout=60.0, settle_s=0.1)
        t_drained = time.monotonic()
        t.join(timeout=60)
        assert drained
        assert len(toks) == 20  # the stream finished intact
        assert t_done[0] is not None and t_drained >= t_done[0] - 0.1, \
            "drain returned while the generation stream was in flight"
    finally:
        remote.close()
        mgr.shutdown()
