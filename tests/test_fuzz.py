"""Randomized concurrency fuzz over the serving-core primitives: invariants
must hold under arbitrary interleavings (bounded runtime for CI)."""

import random
import threading

import numpy as np
import pytest

from helpers_engine import wait_until


def test_pool_fuzz_conservation():
    """Resources are never lost or duplicated under random pop/release/
    detach/timeout traffic."""
    from tpulab.core.pool import Pool
    pool = Pool(range(6))
    detached = []
    lock = threading.Lock()
    errors = []

    def worker(seed):
        rng = random.Random(seed)
        try:
            for _ in range(100):
                op = rng.random()
                try:
                    item = pool.pop(timeout=0.5)
                except TimeoutError:
                    continue
                if op < 0.05 and len(detached) < 2:
                    with lock:
                        if len(detached) < 2:
                            detached.append(item.detach())
                            continue
                    item.release()
                elif op < 0.5:
                    item.release()
                else:
                    del item  # GC-return path
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    [t.start() for t in threads]
    [t.join(timeout=120) for t in threads]
    assert not any(t.is_alive() for t in threads), "pool fuzz worker hung"
    assert not errors
    import gc

    # conservation: pool items + detached == original 6
    def all_home():
        gc.collect()
        return pool.available + len(detached) == 6

    wait_until(all_home, "every pool item is home or detached",
               timeout_s=5, poll_s=0.1)
    got = sorted(detached + [pool.pop(timeout=1).detach()
                             for _ in range(pool.available)])
    assert got == sorted(set(got))  # no duplication


def test_rpc_survives_malformed_wire_payloads():
    """Garbage bytes, undecodable protos, and structurally-lying tensors
    (dims that don't match raw_data, bogus dtype strings) against a LIVE
    server: every abuse yields an error response or RpcError — never a
    wedged worker — and the very next valid request still serves."""
    import grpc
    import numpy as np

    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (SERVICE_NAME,
                                          RemoteInferenceManager)
    from tpulab.rpc.protos import inference_pb2 as pb

    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=2))
    mgr.update_resources()
    mgr.serve(port=0)
    remote = None
    try:
        port = mgr.server.bound_port
        x = np.zeros((1, 28, 28, 1), np.float32)

        def valid_roundtrip():
            r = remote.infer_runner("mnist").infer(Input3=x).result(
                timeout=60)
            assert r["Plus214_Output_0"].shape == (1, 10)

        remote = RemoteInferenceManager(f"localhost:{port}")
        valid_roundtrip()

        # raw garbage at the wire level (identity serializer): the
        # server's proto decode must reject without taking a worker down
        chan = grpc.insecure_channel(f"localhost:{port}")
        raw = chan.unary_unary(f"/{SERVICE_NAME}/Infer",
                               request_serializer=lambda b: b,
                               response_deserializer=lambda b: b)
        rng = np.random.default_rng(0)
        for _ in range(16):
            blob = rng.integers(0, 256, rng.integers(1, 300)).astype(
                np.uint8).tobytes()
            try:
                raw(blob, timeout=30)
            except grpc.RpcError:
                pass  # rejection is the contract; wedging is the bug
        valid_roundtrip()

        # structurally-lying tensors through the real proto
        lies = [
            pb.TensorProto(name="Input3", dtype="float32",
                           dims=[1, 28, 28, 1], raw_data=b"\x00" * 7),
            pb.TensorProto(name="Input3", dtype="not_a_dtype",
                           dims=[1, 28, 28, 1],
                           raw_data=b"\x00" * (28 * 28 * 4)),
            pb.TensorProto(name="Input3", dtype="float32",
                           dims=[-1, 28, 28, 1], raw_data=b""),
            pb.TensorProto(name="wrong_binding", dtype="float32",
                           dims=[1, 28, 28, 1],
                           raw_data=b"\x00" * (28 * 28 * 4)),
        ]
        stub = chan.unary_unary(
            f"/{SERVICE_NAME}/Infer",
            request_serializer=pb.InferRequest.SerializeToString,
            response_deserializer=pb.InferResponse.FromString)
        for t in lies:
            resp = stub(pb.InferRequest(model_name="mnist", inputs=[t]),
                        timeout=60)
            assert resp.status.code != pb.SUCCESS
        valid_roundtrip()
        chan.close()
    finally:
        if remote is not None:
            remote.close()
        mgr.shutdown()


def test_generate_rpc_survives_abusive_requests():
    """Abusive GenerateRequests (steps=0, absurd steps, empty prompt,
    out-of-vocab ids, NaN temperature) each end with a non-SUCCESS final
    response — never a hang or a poisoned lane — and a valid generation
    still streams afterwards."""
    import jax.numpy as jnp
    import numpy as np

    import tpulab
    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import GenerateStreamClient, \
        RemoteInferenceManager

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    cb = ContinuousBatcher(params, n_heads=2, n_layers=2, lanes=2,
                           max_len=64, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = None
    try:
        remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
        client = GenerateStreamClient(remote, "lm")

        from tpulab.rpc.infer_service import GenerationRejected

        def expect_rejection(**kw):
            with pytest.raises(GenerationRejected) as ei:
                list(client.generate(**kw))
            # deterministic request errors must NOT be failed over by
            # routers — the same request is doomed on every replica
            assert not ei.value.retryable, ei.value

        expect_rejection(prompt=[1, 2], steps=0)
        expect_rejection(prompt=[1, 2], steps=10 ** 9)
        expect_rejection(prompt=[], steps=4)
        expect_rejection(prompt=[1, 999999], steps=4)   # out-of-vocab
        expect_rejection(prompt=[-5, 2], steps=4)       # negative id
        expect_rejection(prompt=[1, 2], steps=4,
                         temperature=float("nan"))
        toks = list(client.generate(prompt=[1, 2, 3], steps=6))
        assert len(toks) == 6 and all(0 <= t < 64 for t in toks)
    finally:
        if remote is not None:
            remote.close()
        mgr.shutdown()


def test_batched_runner_fuzz_row_integrity():
    """Random request sizes through the aggregator: every caller gets back
    exactly its own rows."""
    from tpulab.engine import InferenceManager
    from tpulab.engine.batched_runner import BatchedInferRunner
    from tpulab.models.mnist import make_mnist

    mgr = InferenceManager(max_executions=2, max_buffers=6)
    mgr.register_model("mnist", make_mnist(max_batch_size=8))
    mgr.update_resources()
    runner = BatchedInferRunner(mgr, "mnist", window_s=0.005)
    direct = mgr.infer_runner("mnist")
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(10):
                n = int(rng.integers(1, 6))
                # tag each row with a distinctive constant input
                x = np.full((n, 28, 28, 1), float(seed) + 0.01 * n,
                            np.float32)
                out = runner.infer(Input3=x).result(timeout=60)
                want = direct.infer(Input3=x).result(timeout=60)
                np.testing.assert_allclose(out["Plus214_Output_0"],
                                           want["Plus214_Output_0"],
                                           rtol=1e-4, atol=1e-5)
        except Exception as e:  # pragma: no cover
            errors.append((seed, e))

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(6)]
    [t.start() for t in threads]
    [t.join(timeout=300) for t in threads]
    try:
        assert not errors, errors[:2]
        assert not any(t.is_alive() for t in threads)
    finally:
        runner.shutdown()
        mgr.shutdown()
