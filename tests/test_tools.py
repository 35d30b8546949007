"""Tool smoke tests (reference examples/12_ConfigGenerator +
examples/ONNX build.py pipelines)."""

import json
import os
import subprocess
import sys

REPO = __file__.rsplit("/tests/", 1)[0]
ENV = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin", "HOME": "/tmp",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def test_config_generator_cli():
    out = subprocess.run(
        [sys.executable, f"{REPO}/tools/config_generator.py",
         "--model", "mnist", "--max-batch", "4"],
        capture_output=True, text=True, timeout=240, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg = json.loads(out.stdout)
    assert cfg["name"] == "mnist" and cfg["max_batch_size"] == 4
    assert cfg["input"][0]["name"] == "Input3"
    assert cfg["dynamic_batching"]["preferred_batch_size"]


def test_onnx_summary_cli(tmp_path):
    """Import preflight: supported model reports importable (rc 0);
    a model with an unregistered op reports it and exits 2."""
    ref = "/root/reference/models/onnx/mnist-v1.3/model.onnx"
    if os.path.exists(ref):
        out = subprocess.run(
            [sys.executable, f"{REPO}/tools/onnx_summary.py", ref],
            capture_output=True, text=True, timeout=240, env=ENV)
        assert out.returncode == 0, out.stderr[-2000:]
        rep = json.loads(out.stdout)
        assert rep["importable"] and rep["op_histogram"]["Conv"] == 2
        assert rep["inputs"][0]["name"] == "Input3"
    sys.path.insert(0, f"{REPO}/tests")
    try:
        from test_onnx_import import _model_bytes, _node
    finally:
        sys.path.pop(0)
    p = tmp_path / "weird.onnx"
    p.write_bytes(_model_bytes(
        [_node("NonMaxSuppression", ["x"], ["y"])], {},
        [("x", [1, 4])], [("y", [1, 4])]))
    out = subprocess.run(
        [sys.executable, f"{REPO}/tools/onnx_summary.py", str(p)],
        capture_output=True, text=True, timeout=240, env=ENV)
    assert out.returncode == 2
    rep = json.loads(out.stdout)
    assert rep["unsupported_ops"] == ["NonMaxSuppression"]
    assert rep["importable"] is False


def test_build_engine_cli_roundtrip(tmp_path):
    """build -> artifact dir -> loadable engine serving inferences."""
    out = subprocess.run(
        [sys.executable, f"{REPO}/tools/build_engine.py", "--model",
         "mnist", "--max-batch", "2", "--cpu", "--out",
         str(tmp_path / "eng")],
        capture_output=True, text=True, timeout=300, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert (tmp_path / "eng" / "spec.json").exists()
    import numpy as np

    from tpulab.engine import Runtime
    compiled = Runtime().load_engine(str(tmp_path / "eng"))
    logits = compiled(2, {"Input3": np.zeros((2, 28, 28, 1), np.float32)})
    assert next(iter(logits.values())).shape == (2, 10)


def test_gen_inference_pb2_schema_drift_and_roundtrip():
    """tools/gen_inference_pb2.py vs the checked-in inference_pb2 module:
    the full field/enum inventory must match (proto regeneration drift is
    caught in tier-1, not at the next regen), and the admission-control
    schema additions — RESOURCE_EXHAUSTED, retry_after_ms, tenant_id, the
    Status load gauges — round-trip through serialization."""
    import tools.gen_inference_pb2 as gen
    from tpulab.rpc.protos import inference_pb2 as pb

    fd = gen.build_file()
    gen_msgs = {m.name: sorted((f.name, f.number) for f in m.field)
                for m in fd.message_type}
    mod_msgs = {name: sorted((f.name, f.number)
                             for f in getattr(pb, name).DESCRIPTOR.fields)
                for name in gen_msgs}
    assert gen_msgs == mod_msgs, "generator drifted from inference_pb2.py"
    gen_enums = {v.name: v.number
                 for e in fd.enum_type for v in e.value}
    assert gen_enums == dict(pb.StatusCode.items())

    # runtime roundtrips of the admission-control fields
    assert pb.RESOURCE_EXHAUSTED == 6
    st = pb.RequestStatus.FromString(pb.RequestStatus(
        code=pb.RESOURCE_EXHAUSTED, retry_after_ms=125).SerializeToString())
    assert st.code == pb.RESOURCE_EXHAUSTED and st.retry_after_ms == 125
    gr = pb.GenerateRequest.FromString(pb.GenerateRequest(
        prompt=[1, 2], steps=3, tenant_id="team-a").SerializeToString())
    assert gr.tenant_id == "team-a"
    ir = pb.InferRequest.FromString(pb.InferRequest(
        model_name="m", tenant_id="team-a").SerializeToString())
    assert ir.tenant_id == "team-a"
    sr = pb.StatusResponse.FromString(pb.StatusResponse(
        queued_requests=4, free_kv_pages=99).SerializeToString())
    assert sr.queued_requests == 4 and sr.free_kv_pages == 99

    # disaggregation fields (tpulab/disagg): replica role on Status,
    # prefill_only/kv_shipment riding Generate both ways
    sr2 = pb.StatusResponse.FromString(pb.StatusResponse(
        role="prefill").SerializeToString())
    assert sr2.role == "prefill"
    assert pb.StatusResponse().role == ""   # pre-role replica: unified
    dq = pb.GenerateRequest.FromString(pb.GenerateRequest(
        prompt=[1, 2], steps=3, prefill_only=True,
        kv_shipment=b"\x00wire\xff").SerializeToString())
    assert dq.prefill_only and dq.kv_shipment == b"\x00wire\xff"
    dr = pb.GenerateResponse.FromString(pb.GenerateResponse(
        final=True, kv_shipment=b"snap").SerializeToString())
    assert dr.final and dr.kv_shipment == b"snap"
    assert pb.GenerateRequest().kv_shipment == b""  # absent = no shipment

    # durable streams (docs/ROBUSTNESS.md "Stream failover semantics"):
    # resume_length rides the request — prompt already holds the
    # delivered tokens, the server emits from index resume_length
    rr = pb.GenerateRequest.FromString(pb.GenerateRequest(
        prompt=[1, 2, 9, 4], steps=8, resume_length=2).SerializeToString())
    assert rr.resume_length == 2

    # multi-model serving (tpulab/modelstore): residency lists on Status —
    # routers prefer a replica that already has the requested model hot
    mm = pb.StatusResponse.FromString(pb.StatusResponse(
        resident_models=["transformer", "vit_s16"],
        host_models=["transformer_int8"]).SerializeToString())
    assert list(mm.resident_models) == ["transformer", "vit_s16"]
    assert list(mm.host_models) == ["transformer_int8"]
    assert list(pb.StatusResponse().resident_models) == []  # no modelstore
    assert pb.GenerateRequest().resume_length == 0  # absent = fresh request

    # unified HBM economy (tpulab.hbm): the single arbiter headroom gauge
    # rides Status next to free_kv_pages; int64 so an over-committed
    # (negative) discovery reports honestly
    hb = pb.StatusResponse.FromString(pb.StatusResponse(
        free_hbm_bytes=123456789).SerializeToString())
    assert hb.free_hbm_bytes == 123456789
    assert pb.StatusResponse.FromString(pb.StatusResponse(
        free_hbm_bytes=-4096).SerializeToString()).free_hbm_bytes == -4096
    assert pb.StatusResponse().free_hbm_bytes == 0  # no arbiter served

    # prefix-cache effectiveness gauges (tpulab.obs PR): lifetime
    # counters riding Status, parsed per-replica by poll_load — the
    # prefix-affinity-routing signal (ROADMAP item 1)
    pf = pb.StatusResponse.FromString(pb.StatusResponse(
        prefix_hits=7, prefix_lookups=9).SerializeToString())
    assert pf.prefix_hits == 7 and pf.prefix_lookups == 9
    assert pb.StatusResponse().prefix_hits == 0    # no prefix cache
    assert pb.StatusResponse().prefix_lookups == 0

    # fleet drain (tpulab.fleet): a draining replica tells every polling
    # router it must gain nothing new; absent = serving normally
    dn = pb.StatusResponse.FromString(pb.StatusResponse(
        draining=True).SerializeToString())
    assert dn.draining is True
    assert pb.StatusResponse().draining is False

    # offline batch lane (tpulab.batch): the request class rides
    # Generate — "batch" admits strictly below any online priority,
    # from spare capacity only; absent/"" = online (unchanged)
    bc = pb.GenerateRequest.FromString(pb.GenerateRequest(
        prompt=[1, 2], steps=4, request_class="batch").SerializeToString())
    assert bc.request_class == "batch"
    assert pb.GenerateRequest().request_class == ""

    # debugz (tpulab.obs): the Debug unary RPC's request/response — the
    # snapshot is one JSON document (schema tpulab/obs/debugz.py), the
    # profiler fields round-trip, and zero-value defaults read as "no
    # capture asked / no snapshot produced"
    dbq = pb.DebugRequest.FromString(pb.DebugRequest(
        model_name="llm", profile_ticks=4,
        profile_dir="/tmp/prof").SerializeToString())
    assert dbq.model_name == "llm" and dbq.profile_ticks == 4
    assert dbq.profile_dir == "/tmp/prof"
    assert pb.DebugRequest().profile_ticks == 0
    assert pb.DebugRequest().model_name == ""
    dbr = pb.DebugResponse(snapshot_json='{"engines": {}}',
                           profile_dir="/tmp/p")
    dbr.status.code = pb.SUCCESS
    dbr = pb.DebugResponse.FromString(dbr.SerializeToString())
    assert dbr.snapshot_json == '{"engines": {}}'
    assert dbr.profile_dir == "/tmp/p" and dbr.status.code == pb.SUCCESS
    assert pb.DebugResponse().snapshot_json == ""
    assert pb.DebugResponse().profile_dir == ""

    # fleet KV fabric (tpulab.kvfabric): the FetchKV unary — digest in,
    # PR 6 wire-format shipment out; NOT_FOUND is the honest-miss code
    # (publish pending, evicted, unarmed), never an error
    fq = pb.FetchKVRequest.FromString(pb.FetchKVRequest(
        model_name="llm", digest=b"\x01" * 16).SerializeToString())
    assert fq.model_name == "llm" and fq.digest == b"\x01" * 16
    assert pb.FetchKVRequest().digest == b""
    fr = pb.FetchKVResponse(kv_shipment=b"TPKV-blob")
    fr.status.code = pb.NOT_FOUND
    fr = pb.FetchKVResponse.FromString(fr.SerializeToString())
    assert fr.kv_shipment == b"TPKV-blob"
    assert fr.status.code == pb.NOT_FOUND
    assert pb.NOT_FOUND == 7
    assert pb.FetchKVResponse().kv_shipment == b""
