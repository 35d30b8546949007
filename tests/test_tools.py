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


# ------------------------------------------------ tools/xla_clones.py ----

def _xla_clones():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "xla_clones", f"{REPO}/tools/xla_clones.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_xla_clones_names_a_layers_four_products_and_their_parameter():
    """A fixture cut from the by-operation table of PR 60's traced run
    (``chiprun_out/byop60_3160000001.json``, 23 rounds): layer 46's four
    clones of the in-projection and two operations that are no clones.  Two
    of the four read a prefetched copy of the weight (``%custom-call.N``):
    their siblings name the parameter for them."""
    tool = _xla_clones()
    programs = tool.load(f"{REPO}/tests/data/byop_clones_pr61.json")
    assert [p.name for p in programs] == ["jit_paged_mixed_step"]
    assert len(programs[0].insts) == 6
    (group,) = programs[0].clones()
    assert group["names"] == ["fusion.2262.remat", "fusion.2262.remat5",
                              "fusion.2262.remat6", "fusion.2262.remat4"]
    assert group["shape"] == "bf16[544,10304]" and group["product"]
    assert group["parameter"] == "params__layerN____mamba2____in_proj__"
    assert set(group["reads"]) == {"params__layer46____mamba2____in_proj__.1"}
    # 14,380.7 us over 23 runs
    assert abs(group["ms"] - 0.62525) < 1e-4
    out = subprocess.run(
        [sys.executable, f"{REPO}/tools/xla_clones.py",
         f"{REPO}/tests/data/byop_clones_pr61.json"],
        capture_output=True, text=True, timeout=60, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "4 cloned instructions, 0.63 ms a run over 23 runs" in out.stdout
    assert "PRODUCT" in out.stdout and "in_proj" in out.stdout


def test_xla_clones_reads_a_compiled_programs_text(tmp_path):
    """The other input: ``compiled.as_text()``, whose operands carry no
    shapes: a parameter is what the text declares one, a product what the
    fused computation holds, a clone of an element-wise fusion is none."""
    tool = _xla_clones()
    text = """HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[64,96], param_1.2: bf16[8,64]) -> bf16[8,96] {
  %param_1.2 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %param_0.1 = bf16[64,96]{0,1:T(8,128)(2,1)} parameter(0)
  ROOT %convolution.3 = bf16[8,96]{1,0:T(8,128)(2,1)} convolution(%param_1.2, %param_0.1), dim_labels=bf_io->bf
}

%fused_computation.9 (param_0.4: bf16[8,96]) -> bf16[8,32] {
  %param_0.4 = bf16[8,96]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %slice.1 = bf16[8,32]{1,0:T(8,128)(2,1)} slice(%param_0.4), slice={[0:8], [0:32]}
}

ENTRY %main.1 (params__layer3____mamba2____in_proj__.1: bf16[64,96], x.1: bf16[8,64]) -> (bf16[8,32], bf16[8,32]) {
  %params__layer3____mamba2____in_proj__.1 = bf16[64,96]{0,1:T(8,128)(2,1)} parameter(0)
  %x.1 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.5 = bf16[8,96]{1,0:T(8,128)(2,1)S(1)} fusion(%params__layer3____mamba2____in_proj__.1, %x.1), kind=kOutput, calls=%fused_computation.7
  %fusion.6 = bf16[8,32]{1,0:T(8,128)(2,1)} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.9
  %fusion.5.remat = bf16[8,96]{1,0:T(8,128)(2,1)S(1)} fusion(%params__layer3____mamba2____in_proj__.1, %x.1), kind=kOutput, calls=%fused_computation.7
  %fusion.6.remat.1 = bf16[8,32]{1,0:T(8,128)(2,1)} fusion(%fusion.5.remat), kind=kLoop, calls=%fused_computation.9
  ROOT %tuple.1 = (bf16[8,32]{1,0}, bf16[8,32]{1,0}) tuple(%fusion.6, %fusion.6.remat.1)
}
"""
    path = tmp_path / "step.txt"
    path.write_text(text)
    (program,) = tool.load(str(path), "step")
    assert program.parameters >= {"x.1",
                                  "params__layer3____mamba2____in_proj__.1"}
    by_shape = {g["shape"]: g for g in program.clones()}
    assert set(by_shape) == {"bf16[8,96]", "bf16[8,32]"}
    assert by_shape["bf16[8,96]"]["product"]
    assert by_shape["bf16[8,96]"]["parameter"] == \
        "params__layerN____mamba2____in_proj__"
    assert by_shape["bf16[8,96]"]["ms"] is None
    assert not by_shape["bf16[8,32]"]["product"]
    assert by_shape["bf16[8,32]"]["parameter"] is None
    assert "2 cloned instructions (compiled text: no times)" in tool.report(
        [program])
