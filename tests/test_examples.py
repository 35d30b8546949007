"""Example smoke tests (hermetic CPU): the quickstart flow, the CLI bench,
the echo service, and the batching middleman end-to-end."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = __file__.rsplit("/tests/", 1)[0]


def test_30_python_api_quickstart():
    """The notebook flow runs end to end (golden check inside)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tpulab.tpu.platform import force_cpu; force_cpu(1);"
         "import runpy; runpy.run_path("
         f"'{REPO}/examples/30_python_api.py', run_name='__main__')"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "remote == local: OK" in out.stdout


def test_13_onnx_serving_example(tmp_path):
    """ONNX import -> engine artifact -> serve -> golden check over the
    wire (the reference's examples/ONNX workflow); skips gracefully when
    the reference tree is absent."""
    if not os.path.exists("/root/reference/models/onnx/mnist-v1.3"):
        pytest.skip("reference mnist-v1.3 not present")
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, f"{REPO}/examples/13_onnx_serving.py", "--cpu",
         "--engine-dir", str(tmp_path / "eng")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "golden check" in out.stdout and "OK" in out.stdout
    assert (tmp_path / "eng" / "spec.json").exists()


def test_01_echo_service_loopback():
    from examples_helpers import load_example
    mod = load_example("01_basic_grpc")
    from tpulab.rpc import ClientExecutor, ClientUnary, Executor, Server
    from tpulab.rpc.server import AsyncService
    server = Server("127.0.0.1:0", Executor(n_threads=2))
    svc = AsyncService(mod.SERVICE)
    svc.register_rpc("Echo", mod.EchoContext)
    server.register_async_service(svc)
    server.async_start()
    server.wait_until_running()
    try:
        with ClientExecutor(f"127.0.0.1:{server.bound_port}") as cx:
            unary = ClientUnary(cx, f"/{mod.SERVICE}/Echo")
            assert unary.call(b"ping", timeout=10) == b"ping"
    finally:
        server.shutdown()


def test_03_middleman_batches_to_backend():
    """client -> middleman (aggregating) -> backend service."""
    import tpulab
    from examples_helpers import load_example
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc import AsyncService, Executor, Server
    from tpulab.rpc.infer_service import (SERVICE_NAME,
                                          RemoteInferenceManager)
    from tpulab.rpc.protos import inference_pb2 as pb

    backend = tpulab.InferenceManager(max_exec_concurrency=2)
    backend.register_model("mnist", make_mnist(max_batch_size=8))
    backend.update_resources()
    backend.serve(port=0)

    mod = load_example("03_batching_middleman")
    forwarder = mod.BatchingForwarder(
        f"localhost:{backend.server.bound_port}", max_batch=8, window_s=0.02)

    class ForwardContext(mod.Context):
        def execute_rpc(self, request):
            return forwarder.infer(request)

    mm = Server("127.0.0.1:0", Executor(n_threads=8))
    svc = AsyncService(SERVICE_NAME)
    svc.register_rpc("Infer", ForwardContext, pb.InferRequest.FromString,
                     pb.InferResponse.SerializeToString)
    mm.register_async_service(svc)
    mm.async_start()
    mm.wait_until_running()
    try:
        from tpulab.rpc.client import ClientExecutor, ClientUnary
        from tpulab.rpc.infer_service import proto_to_tensor, tensor_to_proto
        with ClientExecutor(f"127.0.0.1:{mm.bound_port}") as cx:
            infer = ClientUnary(cx, f"/{SERVICE_NAME}/Infer",
                                pb.InferRequest.SerializeToString,
                                pb.InferResponse.FromString)
            x = np.zeros((1, 28, 28, 1), np.float32)
            req = pb.InferRequest(model_name="mnist", batch_size=1)
            req.inputs.append(tensor_to_proto("Input3", x))
            futs = [infer.start(req) for _ in range(8)]
            resps = [f.result(timeout=60) for f in futs]
            assert all(r.status.code == pb.SUCCESS for r in resps)
            out = proto_to_tensor(resps[0].outputs[0])
            assert out.shape == (1, 10)
    finally:
        mm.shutdown()
        backend.shutdown()


def test_notebook_multiple_models():
    """The Multiple Models walkthrough runs end to end (per-model budgets,
    mixed traffic, one endpoint serving both)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tpulab.tpu.platform import force_cpu; force_cpu(1);"
         "import runpy; runpy.run_path("
         f"'{REPO}/notebooks/multiple_models.py', run_name='__main__')"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "remote == local for both models" in out.stdout


def test_notebook_onnx_import():
    """The ONNX-import walkthrough runs end to end (golden check, int8,
    portable artifact reload inside)."""
    if not os.path.isdir("/root/reference/models/onnx/mnist-v1.3"):
        pytest.skip("reference mnist-v1.3 not present")
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tpulab.tpu.platform import force_cpu; force_cpu(1);"
         "import runpy; runpy.run_path("
         f"'{REPO}/notebooks/onnx_import.py', run_name='__main__')"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "notebook complete" in out.stdout
    assert "portable artifact reload: OK" in out.stdout


def test_grafana_dashboard_matches_exported_metrics():
    """Every metric the dashboard queries must actually be exported
    (the reference dashboard drifted from its exporter; ours must not)."""
    import json
    import re
    with open(f"{REPO}/examples/deploy/grafana-dashboard.json") as f:
        dash = json.load(f)
    exprs = [t["expr"] for p in dash["panels"] for t in p.get("targets", [])]
    wanted = set()
    for e in exprs:
        wanted.update(re.findall(r"(tpulab_[a-z0-9_]+)", e))
    from tpulab.utils.metrics import (GenerationMetrics, InferenceMetrics,
                                      ReplicaSetMetrics)
    m = InferenceMetrics()
    m.observe_request(0.01, 0.005)  # populate histogram child series
    rm = ReplicaSetMetrics()
    rm.requests.labels(replica="x").inc()  # populate labeled children
    rm.inflight.labels(replica="x").set(0)
    rm.live.labels(replica="x").set(1)
    rm.failovers.inc()
    gm = GenerationMetrics()
    exported = set()
    for reg in (m.registry, rm.registry, gm.registry):
        for metric in reg.collect():
            for s in metric.samples:
                exported.add(s.name)
    missing = {w for w in wanted
               if w not in exported and w.removesuffix("_bucket") + "_bucket"
               not in exported}
    assert not missing, f"dashboard queries unexported metrics: {missing}"


def test_12_binary_codec_service():
    """Codec-agnostic RPC: zero-copy binary payloads through the serde
    hooks (reference 12_FlatBuffers)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, f"{REPO}/examples/12_binary_codec.py", "--cpu"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "binary-codec serving OK" in out.stdout


def test_12_flatbuffers_service():
    """Schema'd zero-copy FlatBuffers payloads (reference 12_FlatBuffers
    example.fbs): round trip + parity with the local pipeline."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, f"{REPO}/examples/12_flatbuffers.py", "--cpu"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "flatbuffers serving OK" in out.stdout


def test_99_run_lb_driver():
    """The LB measurement driver (reference 99_LoadBalancer
    run_loadbalancer.py): 2 replicas, direct + replicaset columns measured,
    envoy skipped gracefully when the binary is absent."""
    import json
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "HOME": "/tmp"}
    # chip-holding replicas need a chip each, named up front: asking for
    # more than --chips lists fails before anything is spawned
    out = subprocess.run(
        [sys.executable, f"{REPO}/examples/99_loadbalancer/run_lb.py",
         "--replicas", "2", "--chips", "0"],
        capture_output=True, text=True, timeout=60, env=env)
    assert out.returncode == 2 and "one process" in out.stderr, out.stderr
    out = subprocess.run(
        [sys.executable, f"{REPO}/examples/99_loadbalancer/run_lb.py",
         "--replicas", "2", "-n", "40", "--cpu", "--json"],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])["lb"]
    assert rec["direct"]["inf_s"] > 0
    assert rec["replicaset"]["inf_s"] > 0
    # split counts the siege + warm + latency-probe requests; all of them
    # completed through the set, spread over both replicas
    assert sum(rec["replicaset"]["split"]) >= 40
    assert all(s > 0 for s in rec["replicaset"]["split"])
    assert "overhead_us_vs_direct" in rec["replicaset"]
    assert "skipped" in rec["envoy"] or rec["envoy"]["inf_s"] > 0


def test_06_stream_client_pipelines():
    """Standalone streaming middleman client (reference 04_Middleman
    middleman-client)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, f"{REPO}/examples/06_stream_client.py", "--cpu",
         "--requests", "16"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "streamed:" in out.stdout


def test_02_inference_service_cli():
    """The flagship serving CLI boots, serves, and exports metrics (this
    example regressed silently in round 1 — no test drove its main())."""
    import urllib.request
    from tests.conftest import free_port
    port, mport = free_port(), free_port()
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
    proc = subprocess.Popen(
        [sys.executable, f"{REPO}/examples/02_inference_service.py",
         "--cpu", "--model", "mnist", "--max-batch-size", "2",
         "--port", str(port), "--metrics-port", str(mport), "--batching"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    try:
        from tpulab.rpc.infer_service import RemoteInferenceManager
        deadline = time.time() + 240
        remote = None
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"server died: {proc.communicate()[1][-2000:]}")
            candidate = RemoteInferenceManager(f"localhost:{port}")
            try:
                candidate.get_models()
                remote = candidate  # ready only once a call succeeded
                break
            except Exception:
                candidate.close()
                time.sleep(2)
        assert remote is not None, "server never came up"
        out = remote.infer_runner("mnist").infer(
            Input3=np.zeros((1, 28, 28, 1), np.float32)).result(timeout=120)
        assert out["Plus214_Output_0"].shape == (1, 10)
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/metrics", timeout=10).read().decode()
        assert "tpulab_request_total" in metrics
        remote.close()
    finally:
        proc.terminate()  # SIGTERM -> drain -> clean exit (k8s path)
        rc = proc.wait(timeout=30)
    assert rc == 0, (rc, proc.stderr.read()[-1000:] if proc.stderr else "")
    assert "SIGTERM: draining" in proc.stdout.read()


def test_model_store_roundtrip(tmp_path):
    """Deployment companion: engine artifact push/pull through the object
    store (file backend; reference Deployment/ObjectStore flow) and a
    source-free load of the pulled artifact."""
    from tpulab.engine.runtime import Runtime
    from tpulab.models.mnist import make_mnist
    from tools.model_store import pull, push

    rt = Runtime()
    compiled = rt.compile_model(make_mnist(max_batch_size=2))
    art = tmp_path / "art"
    rt.save_engine(compiled, str(art))
    store = tmp_path / "store" / "mnist-v1"
    push(str(art), str(store))
    dest = tmp_path / "pulled"
    pull(str(store), str(dest))
    loaded = rt.load_engine(str(dest))  # portable modules, no apply_fn
    out = loaded(1, {"Input3": np.zeros((1, 28, 28, 1), np.float32)})
    assert out["Plus214_Output_0"].shape == (1, 10)


def test_image_client_preprocessing(tmp_path):
    """ImageClient companion: JPEG decode + center-crop resize to the
    serving tensor (reference Deployment/ImageClient)."""
    from PIL import Image
    from tools.image_client import load_image
    img = Image.fromarray(
        np.random.default_rng(0).integers(0, 255, (300, 400, 3),
                                          np.uint8).astype(np.uint8))
    p = tmp_path / "t.jpg"
    img.save(p)
    u8 = load_image(str(p), size=224, dtype=np.uint8)
    assert u8.shape == (224, 224, 3) and u8.dtype == np.uint8
    f32 = load_image(str(p), size=224, dtype=np.float32)
    assert f32.dtype == np.float32 and abs(float(f32.mean())) < 3.0


@pytest.mark.slow  # heavyweight e2e; tier-1 runtime headroom (see ROADMAP)
def test_notebook_llm_serving():
    """The LLM-serving tour runs end to end (continuous batching, prefix
    cache, streaming, speculative decoding)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tpulab.tpu.platform import force_cpu; force_cpu(1);"
         "import runpy; runpy.run_path("
         f"'{REPO}/notebooks/llm_serving.py', run_name='__main__')"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "streamed as decoded" in out.stdout
    assert "page hits" in out.stdout
    assert out.stdout.strip().endswith("done")


def _spawn_llm_server(env, *extra_args, oneshot=True):
    return subprocess.Popen(
        [sys.executable, f"{REPO}/examples/07_llm_server.py", "--cpu",
         "--port", "0", "--max-len", "128", "--lanes", "2",
         *(["--oneshot"] if oneshot else []), *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)


@pytest.mark.slow  # heavyweight e2e; tier-1 runtime headroom (see ROADMAP)
def test_07_llm_server_metrics_export():
    """--metrics-port: tpulab_llm_* series reflect real serving (tokens
    generated, prefix-cache state) after a generation completes."""
    import urllib.request
    from tests.conftest import free_port
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    mport = free_port()
    # no --oneshot: the server must outlive the request for the scrape
    srv = _spawn_llm_server(env, "--metrics-port", str(mport),
                            oneshot=False)
    try:
        port = _wait_llm_port(srv)
        out = subprocess.run(
            [sys.executable, f"{REPO}/examples/07_llm_server.py", "--cpu",
             "--connect", f"localhost:{port}", "--prompt", "5,6,7",
             "--steps", "6"],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        deadline = time.time() + 30  # poller samples every 2s
        body = ""
        while time.time() < deadline:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=10
            ).read().decode()
            if ("tpulab_llm_tokens_total 6.0" in body
                    and "tpulab_llm_requests_completed_total 1.0" in body):
                break  # both settled: no race with deferred completion
            time.sleep(1)
        assert "tpulab_llm_tokens_total 6.0" in body, body[-1200:]
        assert "tpulab_llm_requests_completed_total 1.0" in body
        import re
        free = float(re.search(r"^tpulab_llm_free_pages (\S+)$", body,
                               re.M).group(1))
        assert free > 0, "all pages released after completion"
    finally:
        srv.kill()


def _wait_llm_port(srv, deadline_s=120.0):
    """Port from the server banner, deadline ENFORCED — a server that
    stays alive but never prints must fail the test, not hang readline()
    (and with it the whole pytest run) forever.  A daemon pump thread
    owns the blocking reads (select on the raw fd would lie: readline's
    TextIOWrapper buffer can already hold the banner), and keeps draining
    the merged stdout/stderr pipe after the banner so the server can
    never block on a full pipe."""
    import queue
    q = queue.Queue()

    def pump():
        for line in srv.stdout:
            q.put(line)

    threading.Thread(target=pump, daemon=True).start()
    seen, deadline = [], time.time() + deadline_s
    while time.time() < deadline:
        try:
            line = q.get(timeout=max(0.0, min(1.0,
                                              deadline - time.time())))
        except queue.Empty:
            if srv.poll() is not None:
                raise AssertionError("server died at startup:\n"
                                     + "".join(seen))
            continue
        seen.append(line)
        if "LLM server on :" in line:
            return line.split("LLM server on :")[1].split()[0]
    raise AssertionError("server never came up:\n" + "".join(seen))


def test_07_llm_server_end_to_end():
    """The LLM server example: int8 weights + fp8 KV + prefix cache behind
    the Generate RPC, driven by its own client mode across processes."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    srv = _spawn_llm_server(env, "--int8", "--kv-fp8")
    try:
        port = _wait_llm_port(srv)
        out = subprocess.run(
            [sys.executable, f"{REPO}/examples/07_llm_server.py", "--cpu",
             "--connect", f"localhost:{port}", "--prompt", "5,6,7",
             "--steps", "6", "--temperature", "0.7", "--seed", "3"],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        toks = out.stdout.split("\n")[0].split()
        assert len(toks) == 6 and out.stdout.strip().endswith("done")
        assert srv.wait(timeout=60) == 0  # oneshot exit
    finally:
        srv.kill()


def test_07_llm_server_replicated_client():
    """07's comma-separated --connect: two real server processes, the
    client routes through GenerationReplicaSet and reports per-replica
    counts (the generation analog of the 98/99 scale-out scripts)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    srvs = []
    try:
        for _ in range(2):  # spawn INSIDE the try: a failed second spawn
            srvs.append(_spawn_llm_server(env))  # must not leak the first
        ports = [_wait_llm_port(srv) for srv in srvs]
        # whitespace after the comma exercises the tolerant parsing
        target = f"localhost:{ports[0]}, localhost:{ports[1]}"
        out = subprocess.run(
            [sys.executable, f"{REPO}/examples/07_llm_server.py", "--cpu",
             "--connect", target, "--prompt", "5,6,7", "--steps", "6"],
            capture_output=True, text=True, timeout=180, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        toks = out.stdout.split("\n")[0].split()
        assert len(toks) == 6, out.stdout
        assert "requests per replica" in out.stdout
    finally:
        for srv in srvs:
            srv.kill()


def test_notebook_scale_out_serving():
    """The scale-out tour runs end to end (replica routing, failover,
    affinity, exactly-once stream replay — assertions inside)."""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "TPULAB_FORCE_CPU": "1", "HOME": "/tmp"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tpulab.tpu.platform import force_cpu; force_cpu(1);"
         "import runpy; runpy.run_path("
         f"'{REPO}/notebooks/scale_out_serving.py', run_name='__main__')"],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "scale-out serving tour complete" in out.stdout


def test_12_flatbuffers_rejects_malformed_payloads():
    """Untrusted wire bytes must surface as clean RPC errors — raised at
    whichever layer catches them (empty buffers in the deserializer,
    garbage/truncation during lazy field access, a decoded-but-empty
    message at model lookup) — and never crash the server, verified by a
    good request succeeding afterwards."""
    import grpc

    import tpulab
    from examples_helpers import load_example
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc import ClientExecutor, ClientUnary

    mod = load_example("12_flatbuffers")
    mgr = tpulab.InferenceManager(max_exec_concurrency=1, max_buffers=2)
    mgr.register_model("mnist", make_mnist(max_batch_size=2))
    mgr.update_resources()
    server = mod.build_service(mgr)
    server.async_start()
    server.wait_until_running()
    try:
        x = np.zeros((1, 28, 28, 1), np.float32)
        good = mod.encode_request("mnist", msg_id=1, Input3=x)
        with ClientExecutor(f"127.0.0.1:{server.bound_port}") as cx:
            infer = ClientUnary(cx, f"/{mod.SERVICE}/Infer",
                                request_serializer=lambda b: b,
                                response_deserializer=lambda b: b)
            for bad in (b"", b"\x00" * 4, b"garbage-not-a-flatbuffer",
                        good[: len(good) // 3]):
                with pytest.raises(grpc.RpcError) as exc_info:
                    infer.call(bad, timeout=30)
                # a clean rejection, not a server stall
                assert (exc_info.value.code()
                        is not grpc.StatusCode.DEADLINE_EXCEEDED)
            # the server survived every malformed payload
            resp = mod.InferResponseReader(infer.call(good, timeout=60))
            assert resp.tensors()["Plus214_Output_0"].shape == (1, 10)
    finally:
        server.shutdown()
        mgr.shutdown()
