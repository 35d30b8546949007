"""ResNet-50 serving throughput per chip, plus one row per subsystem.

Mirrors the reference's headline configuration (examples/00_TensorRT README:
RN50 INT8 batch=1, pipelined H2D/compute/D2H, synthetic data -> 953.4 inf/s on
V100): uint8 image bytes in, on-device normalization, full
InferenceManager/InferRunner pipeline (staging buffers -> async H2D ->
bucketed compiled dispatch -> coalesced D2H).

Needs the chip: exits 2 at once when JAX finds no TPU (through the chip
tool: ``chiprun -- python bench.py``).  Prints ONE JSON line
{"metric", "value", "unit", "vs_baseline", "device", "details"}; a row
that raises is named in ``details.failed_rows`` and the exit code is 1.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

BASELINE_INF_PER_SEC = 953.4  # reference examples/00_TensorRT/README.md:46

REPO = os.path.dirname(os.path.abspath(__file__))

_details: dict = {}
_failed_rows: list = []


def _phase(name: str) -> None:
    print(f"# phase {name}", file=sys.stderr, flush=True)


def _record(**kv) -> None:
    _details.update(kv)
    for name, row in kv.items():
        _check_row_errors(name, row)


def _row_failed(name: str, exc: BaseException) -> None:
    """A row that raised: the run goes on to the other rows, but the
    failure is in the JSON line and in the exit code."""
    traceback.print_exc(file=sys.stderr)
    print(f"# row {name} FAILED: {exc!r}", file=sys.stderr, flush=True)
    _failed_rows.append(name)


def _check_row_errors(name: str, row) -> None:
    """The benchmark_* helpers report a mode that raised as an ``error``
    / ``*_error`` key inside the row; that is a failed row here."""
    def walk(x):
        if isinstance(x, dict):
            return any(str(k).endswith("error") or walk(v)
                       for k, v in x.items())
        if isinstance(x, (list, tuple)):
            return any(walk(v) for v in x)
        return False
    if walk(row):
        print(f"# row {name} FAILED: {row!r}", file=sys.stderr, flush=True)
        _failed_rows.append(name)


def _emit_line(device: dict) -> None:
    d = dict(_details)
    headline = d.get("b1_inf_s", 0.0)
    d.setdefault("baseline",
                 "examples/00_TensorRT RN50 INT8 b=1 V100 = 953.4 inf/s")
    d["failed_rows"] = list(_failed_rows)
    print(json.dumps({
        "metric": "resnet50_infer_per_sec_per_chip_b1",
        "value": round(headline, 1),
        "unit": "inf/s",
        "vs_baseline": round(headline / BASELINE_INF_PER_SEC, 4),
        "device": device,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "details": d,
    }), flush=True)


def main() -> int:
    import jax

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"bench.py needs a TPU; jax found platform={dev0.platform!r} "
              f"({dev0.device_kind}). Run it through the chip tool.",
              file=sys.stderr)
        return 2
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}

    import numpy as np
    from tpulab.engine import InferBench, InferenceManager
    from tpulab.models.resnet import make_resnet
    from tpulab.tpu.device_info import DeviceInfo
    from tpulab.tpu.platform import enable_compilation_cache

    enable_compilation_cache()
    # the native host core is used when its library is on the loader's
    # path (TPULAB_NATIVE_LIB or cpp/build/, see tpulab.native); the row
    # says which host core this run measured
    from tpulab import native
    _record(native_core=native.enabled())
    # host<->device link figures: the pipeline numbers below are bounded
    # by these as well as by the chip
    _phase("link_probe")
    try:
        from tpulab.tpu.platform import local_device
        dev = local_device(0)
        small = np.zeros((8,), np.float32)
        d_small = jax.device_put(small, dev)
        np.asarray(d_small)  # warm
        rtts = []
        for _ in range(10):
            t0 = time.perf_counter()
            np.asarray(jax.device_put(small, dev))
            rtts.append((time.perf_counter() - t0) * 1e3)
        big = np.zeros((8 << 20,), np.uint8)  # 8 MB
        np.asarray(jax.device_put(big, dev)[:1])  # warm slice program
        t0 = time.perf_counter()
        d_big = jax.device_put(big, dev)
        np.asarray(d_big[:1])
        h2d_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(d_big)
        d2h_s = time.perf_counter() - t0
        h2d_mib_s = 8 / h2d_s  # the probe ships 8<<20 bytes: MiB/s
        # the b=1 pipeline ships one 224x224x3 uint8 image per request
        # H2D: the measured link bandwidth bounds the headline at
        # ceiling = bw / payload regardless of chip speed.  Binary units
        # on BOTH sides — mixing MiB/s with decimal MB would overstate
        # the ceiling by ~4.9%
        payload_mib = 224 * 224 * 3 / (1 << 20)
        _record(link={"rtt_ms_p50": round(float(np.median(rtts)), 2),
                      "h2d_mb_s": round(h2d_mib_s, 1),
                      "d2h_mb_s": round(8 / d2h_s, 1),
                      "b1_payload_kib": round(payload_mib * 1024, 1),
                      "b1_link_ceiling_inf_s": round(
                          h2d_mib_s / payload_mib, 1)})
    except Exception as e:
        _row_failed("link", e)
    t_start = time.time()  # compile_s spans every model compile
    _phase("compile")
    # power-of-2 buckets: the dynamic batcher's groups land on (or near) an
    # exact bucket instead of padding to 128 — on a bandwidth-limited link
    # a 32-row group padded to 128 ships 4x the bytes it needs
    buckets = [1, 2, 4, 8, 16, 32, 64, 128]
    sweep = ((8, 5.0), (128, 10.0))
    model = make_resnet(depth=50, max_batch_size=buckets[-1],
                        input_dtype=np.uint8, batch_buckets=buckets)
    # calibrated full-INT8 (W8A8) servable twin (VERDICT r3 #9: the
    # reference headline IS int8 END-TO-END, not compute-only) — same
    # weights, int8 kernels + per-unit activation scales; served next to
    # the bf16 model through the identical pipeline and gRPC path
    qparams = None
    _phase("calibrate_int8")
    try:
        from tpulab.models.quantization import (
            calibrate_resnet, quantize_resnet_params_w8a8)
        cal = np.random.default_rng(0).standard_normal(
            (4, 224, 224, 3)).astype(np.float32)
        qparams = quantize_resnet_params_w8a8(
            model.params, calibrate_resnet(model.params, [cal]))
    except Exception as e:
        _row_failed("int8_calibration", e)
    mgr = InferenceManager(max_executions=8, max_buffers=32)
    mgr.register_model("rn50", model)
    if qparams is not None:
        try:
            # coarser bucket plan than bf16: 3 extra compiles, not 8
            mgr.register_model("rn50i8", make_resnet(
                depth=50, max_batch_size=64, input_dtype=np.uint8,
                batch_buckets=[1, 16, 64], params=qparams))
        except Exception as e:  # int8 must never sink the bf16 number
            qparams = None
            _row_failed("int8_registration", e)
    # identity model with the rn50 payload: the gRPC row minus compute.
    # health floor -> echo rate -> rn50 rate attributes the serving path
    # (RPC machinery vs payload handling vs model) in ONE capture
    from tpulab.engine.model import IOSpec, Model
    mgr.register_model("echo", Model(
        "echo", lambda p, x: {"out": x["input"]}, {},
        [IOSpec("input", (224, 224, 3), np.uint8)],
        [IOSpec("out", (224, 224, 3), np.uint8)],
        max_batch_size=8, batch_buckets=[1, 8]))
    mgr.update_resources()
    # the b=1 headline rides its OWN manager: staging bundles are sized to
    # the largest registered bucket, so a deep (256) pipeline is only
    # affordable on a bucket-1 model (~0.6 MB/bundle, not ~20 MB)
    _phase("compile_b1")
    model_b1 = make_resnet(depth=50, max_batch_size=1,
                           input_dtype=np.uint8, batch_buckets=[1],
                           params=model.params)
    mgr_b1 = InferenceManager(max_executions=16,
                              max_buffers=288)
    mgr_b1.register_model("rn50", model_b1)
    if qparams is not None:
        try:
            mgr_b1.register_model("rn50i8", make_resnet(
                depth=50, max_batch_size=1, input_dtype=np.uint8,
                batch_buckets=[1], params=qparams))
        except Exception as e:
            qparams = None
            _row_failed("int8_b1_registration", e)
    # tiny identity model: host-pipeline cost probe (see pipeline_floor)
    mgr_b1.register_model("null", Model(
        "null", lambda p, x: {"out": x["in"]}, {},
        [IOSpec("in", (8,), np.float32)], [IOSpec("out", (8,), np.float32)],
        max_batch_size=1, batch_buckets=[1]))
    mgr_b1.update_resources()
    _record(compile_s=round(time.time() - t_start, 1))

    bench = InferBench(mgr)
    bench_b1 = InferBench(mgr_b1)
    _phase("pipeline_b1")
    # dispatch-depth sweep at b=1: record the overlap curve, serve the
    # headline from the best depth (reference --buffers sweep)
    dsweep = {}
    for d in (16, 32, 64, 128, 256):
        _phase(f"pipeline_b1_depth{d}")
        rd = bench_b1.run("rn50", batch_size=1, seconds=3.0, warmup=2,
                          depth=d)
        dsweep[d] = round(rd["inferences_per_second"], 1)
    depth = max(dsweep, key=dsweep.get)
    _record(b1_depth_sweep=dsweep, b1_depth_best=depth)
    r = bench_b1.run("rn50", batch_size=1, seconds=5.0, warmup=2,
                     depth=depth)
    _record(b1_inf_s=round(r["inferences_per_second"], 1))
    if qparams is not None:
        # the int8 model through the IDENTICAL full pipeline at the
        # bf16-best depth — the dtype-for-dtype end-to-end comparison
        _phase("pipeline_b1_int8")
        try:
            ri = bench_b1.run("rn50i8", batch_size=1, seconds=5.0,
                              warmup=2, depth=depth)
            _record(b1_int8_inf_s=round(
                ri["inferences_per_second"], 1))
        except Exception as e:
            _row_failed("int8_pipeline", e)
    for b, secs in sweep:
        _phase(f"pipeline_b{b}")
        r = bench.run("rn50", batch_size=b, seconds=secs, warmup=2)
        _record(**{f"b{b}_inf_s": round(r["inferences_per_second"], 1)})
    # host overhead: (a) pure host staging cost — pool pop, bindings carve,
    # input copy, release, NO device work; (b) the null-model full pipeline
    # at depth 256, whose inverse throughput upper-bounds the serialized
    # per-request host cost once 256-deep overlap hides dispatch latency
    _phase("pipeline_floor")
    t_host = []
    img_null = np.zeros((1, 8), np.float32)
    for _ in range(200):
        t0 = time.perf_counter()
        bi = mgr_b1.get_buffers()
        bd = bi.get().create_bindings(mgr_b1.model("null"), 1)
        bd.set_input("in", img_null)
        bd.release()
        bi.release()
        t_host.append((time.perf_counter() - t0) * 1e6)
    _record(host_staging_us_per_req=round(float(np.median(t_host)), 1))
    fl = bench_b1.run("null", batch_size=1, seconds=3.0, warmup=4,
                      depth=256)
    _record(null_pipeline_us_per_req_depth256=round(
        1e6 / max(fl["inferences_per_second"], 1e-9), 1))
    _phase("latency_b1")
    lat = bench.latency("rn50", batch_size=1, iterations=40)
    _record(p50_ms_b1=round(lat["p50_ms"], 2),
            p99_ms_b1=round(lat["p99_ms"], 2))

    # compute-only ceiling (device-resident input, iterations chained
    # inside ONE compiled lax.scan): the scan carries a data dependency
    # through every iteration, so none can be elided or reordered, and the
    # timed region ends with a host fetch of the per-iteration logit trace.
    _phase("compute_only")
    cb = buckets[-1]
    n = 30
    apply_fn = model.apply_fn

    @jax.jit
    def _chain(params, x):
        def body(carry, _):
            out = apply_fn(params, {"input": carry})
            logit = next(iter(out.values()))[0, 0]
            # fold a zero derived from the output back into the input:
            # forces sequential execution of every iteration
            carry = carry + (logit * 0).astype(carry.dtype)
            return carry, logit
        _, ls = jax.lax.scan(body, x, None, length=n)
        return ls

    dev_img = jax.device_put(np.zeros((cb, 224, 224, 3), np.uint8),
                             mgr.device)
    dev_params = mgr.compiled("rn50").device_params
    np.asarray(_chain(dev_params, dev_img))  # compile + warm (fetch fence)
    t0 = time.perf_counter()
    np.asarray(_chain(dev_params, dev_img))
    _record(**{f"compute_only_b{cb}_inf_s": round(
        cb * n / (time.perf_counter() - t0), 1)})

    # full-INT8 (W8A8) compute ceiling: int8 x int8 -> int32 convs on the
    # MXU — the dtype-for-dtype comparison against the reference's INT8
    # headline (examples/ONNX/resnet50/int8.py calibrated engines)
    if qparams is not None:
        _phase("compute_only_w8a8")
        try:
            qp = jax.device_put(qparams, mgr.device)
            np.asarray(_chain(qp, dev_img))  # compile + warm
            t0 = time.perf_counter()
            np.asarray(_chain(qp, dev_img))
            _record(**{f"compute_only_w8a8_b{cb}_inf_s": round(
                cb * n / (time.perf_counter() - t0), 1)})
        except Exception as e:
            _row_failed("w8a8", e)

    # MFU (VERDICT r4 #4: the driver's perf axis, reported not derived):
    # model FLOPs from XLA's own cost analysis of the compiled bucket
    # executable, peak from the public per-chip spec table.  int8 rows
    # divide by the int8 peak — dtype-for-dtype honesty.
    _phase("mfu")
    try:
        flops_b1 = mgr_b1.compiled("rn50").flops(1)
        flops_bN = mgr.compiled("rn50").flops(cb)
        peak_bf16 = DeviceInfo.peak_flops("bf16")
        peak_int8 = DeviceInfo.peak_flops("int8")
        if flops_b1 and peak_bf16:
            d = dict(_details)
            mfu = {"model_gflops_per_inf": round(flops_b1 / 1e9, 2),
                   "peak_tflops_bf16": round(peak_bf16 / 1e12, 1)}
            if peak_int8:
                mfu["peak_tflops_int8"] = round(peak_int8 / 1e12, 1)

            def pct(rate, flops_per_inf, peak):
                return round(100.0 * rate * flops_per_inf / peak, 2)

            if d.get("b1_inf_s"):
                mfu["e2e_b1_pct"] = pct(d["b1_inf_s"], flops_b1, peak_bf16)
            if flops_bN and d.get(f"b{cb}_inf_s"):
                mfu[f"e2e_b{cb}_pct"] = pct(d[f"b{cb}_inf_s"],
                                            flops_bN / cb, peak_bf16)
            if flops_bN and d.get(f"compute_only_b{cb}_inf_s"):
                mfu[f"compute_only_b{cb}_pct"] = pct(
                    d[f"compute_only_b{cb}_inf_s"], flops_bN / cb, peak_bf16)
            if peak_int8 and d.get(f"compute_only_w8a8_b{cb}_inf_s"):
                # int8 executables report their own (int-op) cost analysis;
                # reuse the bf16 FLOP count so the ratio is op-for-op
                mfu[f"compute_only_w8a8_b{cb}_pct"] = pct(
                    d[f"compute_only_w8a8_b{cb}_inf_s"],
                    flops_bN / cb, peak_int8)
            if peak_int8 and d.get("b1_int8_inf_s"):
                mfu["e2e_int8_b1_pct"] = pct(d["b1_int8_inf_s"], flops_b1,
                                             peak_int8)
            _record(mfu=mfu)
    except Exception as e:
        _row_failed("mfu", e)

    # per-stage decomposition at b=1, sequential (the measured answer to
    # "where does the millisecond go": host staging, H2D, compute, D2H)
    _phase("stage_decomposition")
    comp1 = mgr.compiled("rn50")
    img1 = np.random.default_rng(0).integers(
        0, 255, (1, 224, 224, 3)).astype(np.uint8)
    stages = {"host_us": [], "h2d_ms": [], "compute_ms": [], "d2h_ms": []}
    for _ in range(20):
        t0 = time.perf_counter()
        bi = mgr.get_buffers()
        bd = bi.get().create_bindings(model, 1)
        bd.set_input("input", img1)
        t1 = time.perf_counter()
        dev = jax.device_put(bd.host_inputs["input"], mgr.device)
        np.asarray(dev[0, 0, 0, 0])   # fetch ends the H2D stage
        t2 = time.perf_counter()
        out = comp1(1, {"input": dev})
        np.asarray(next(iter(out.values()))[0, 0])
        t3 = time.perf_counter()
        _ = {k: np.asarray(v) for k, v in out.items()}
        t4 = time.perf_counter()
        bd.release()
        bi.release()
        stages["host_us"].append((t1 - t0) * 1e6)
        stages["h2d_ms"].append((t2 - t1) * 1e3)
        stages["compute_ms"].append((t3 - t2) * 1e3)
        stages["d2h_ms"].append((t4 - t3) * 1e3)
    _record(stage_p50={k: round(float(np.median(v)), 3)
                       for k, v in stages.items()})

    # paged-decode kernel row: pallas ragged kernel vs XLA gather at B=8,
    # 2k context — the beyond-reference serving differentiator
    try:
        _phase("paged_decode_kernel")
        from tpulab.engine.paged import benchmark_decode_kernel_sweep
        rows = benchmark_decode_kernel_sweep()
        _record(paged_decode=rows[0], paged_decode_sweep=rows)
    except Exception as e:
        _row_failed("paged_decode", e)
    try:
        _phase("llm_decode_w8a16")
        from tpulab.engine.paged import benchmark_llm_decode
        _record(llm_decode=benchmark_llm_decode())
    except Exception as e:
        _row_failed("llm_decode", e)

    # LLM serving tail latency: TTFT / inter-token p50+p99 from the
    # batcher-observed GenerationMetrics reservoirs (the distributions the
    # deep-learning-inference-benchmark line says actually distinguish
    # serving stacks — means hide the tail).
    _phase("llm_latency")
    try:
        import jax.numpy as jnp
        from prometheus_client import CollectorRegistry

        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.transformer import init_transformer_params
        from tpulab.utils.metrics import GenerationMetrics

        gm = GenerationMetrics(registry=CollectorRegistry())
        lm_params = init_transformer_params(vocab=256, d_model=64,
                                            n_heads=4, n_layers=2, d_ff=256)
        cb = ContinuousBatcher(lm_params, n_heads=4, n_layers=2, lanes=4,
                               max_len=64, page_size=8,
                               compute_dtype=jnp.float32)
        try:
            n_req, steps = (16, 32)
            rng = np.random.default_rng(0)
            # warmup BEFORE attaching metrics: prefill/decode compiles must
            # not pollute the recorded TTFT tail
            cb.submit(rng.integers(0, 256, (8,), np.int32),
                      steps).result(timeout=300)
            cb.metrics = gm
            futs = [cb.submit(rng.integers(0, 256, (8,), np.int32), steps)
                    for _ in range(n_req)]
            for f in futs:
                f.result(timeout=300)
        finally:
            cb.shutdown()
        tq, iq = gm.ttft_quantiles(), gm.itl_quantiles()
        _record(llm_latency={
            "n_requests": n_req, "steps": steps, "lanes": 4,
            "ttft_ms_p50": round(tq["p50"] * 1e3, 2),
            "ttft_ms_p99": round(tq["p99"] * 1e3, 2),
            "itl_ms_p50": round(iq["p50"] * 1e3, 2),
            "itl_ms_p99": round(iq["p99"] * 1e3, 2),
            "source": "GenerationMetrics reservoirs (batcher-observed)"})
    except Exception as e:
        _row_failed("llm_latency", e)

    # multi-step fused decode (docs/PERFORMANCE.md): the same paged
    # workload at decode-block sizes K=1 vs K>1: the serving loop pays one
    # blocking fetch per block, and K cuts fetches to ceil(steps/K) per
    # request.
    _phase("decode_dispatch")
    try:
        from tpulab.engine.paged import benchmark_decode_dispatch
        _record(decode_dispatch=benchmark_decode_dispatch(
            ks=(1, 4, 8, 16),
            steps=48))
    except Exception as e:
        _row_failed("decode_dispatch", e)

    # tiered KV cache (docs/PERFORMANCE.md "KV tiering"): the same
    # preemption-heavy workload under ~2x KV oversubscription with the
    # host-memory offload tier on vs off.  The claim tracked: with the
    # tier on, preemptions swap instead of recompute — re-prefill
    # dispatches collapse toward zero.  On CPU jit the dispatch counts
    # are the signal; on-device every avoided re-prefill is a full
    # prompt+generated forward not burned twice, so goodput is the
    # headline there.
    _phase("kv_offload")
    try:
        from tpulab.kvcache import benchmark_kv_offload
        _record(kv_offload=benchmark_kv_offload(
            n_low=4, n_hi=4,
            steps=20))
    except Exception as e:
        _row_failed("kv_offload", e)

    # multi-model serving (docs/SERVING.md "Multi-model serving"): an
    # interleaved two-model trace (transformer LLM + ViT classifier)
    # under HBM weight pressure — the budget holds ONE model, so every
    # switch swaps.  Multiplexer on (host-tier swap-ins) vs off (serial
    # cold rebuild per switch).  The claims tracked: swap-in beats cold
    # rebuild, evictions ride the write-behind path, and both modes emit
    # bit-identical outputs (parity).
    _phase("multi_model")
    try:
        from tpulab.modelstore import benchmark_multi_model
        _record(multi_model=benchmark_multi_model(
            switches=6,
            steps=8))
    except Exception as e:
        _row_failed("multi_model", e)

    # unified HBM economy (docs/PERFORMANCE.md "HBM economy"): a mixed
    # model-swap + KV-burst trace under device-HBM oversubscription —
    # the budget holds EITHER the burst's grown page pool OR the second
    # model's weights, never both.  Arbiter on (the pool grows by
    # evicting the cold model; a model acquire demotes idle KV and
    # shrinks the pool back) vs today's static split (fixed small pool,
    # model always resident, burst serialized).  The claims tracked:
    # goodput >= the static split under mixed pressure, both pressure
    # directions fire (demotions AND evictions > 0), and tokens/outputs
    # are bit-identical in both modes (parity).
    _phase("hbm_arbiter")
    try:
        from tpulab.hbm import benchmark_hbm_arbiter
        _record(hbm_arbiter=benchmark_hbm_arbiter(n_llm=12))
    except Exception as e:
        _row_failed("hbm_arbiter", e)

    # observability overhead (docs/OBSERVABILITY.md "Flight recorder"):
    # the standard paged workload with the flight recorder armed AND a
    # debugz poller pulling live snapshots vs bare.  The claims tracked:
    # tokens are bit-identical armed vs off (the recorder observes,
    # never steers), tok/s overhead stays < 5%, and the per-request
    # record-assembly p99 (ms) is the direct cost figure.
    _phase("obs_overhead")
    try:
        from tpulab.obs import benchmark_obs_overhead
        _record(obs_overhead=benchmark_obs_overhead(
            n_requests=16,
            steps=32))
    except Exception as e:
        _row_failed("obs_overhead", e)

    # disaggregated prefill/decode (docs/SERVING.md "Replica roles"):
    # the same prefill-heavy trace served by one unified pool vs a
    # prefill replica shipping finished KV over the host tier's wire
    # form to a decode replica.  The claim tracked: the decode replica
    # admits with ZERO prefill dispatches and its ITL tail stops paying
    # for other requests' prompt forwards.  On CPU jit the dispatch
    # counts + tail ratio are the signal; on-device the p99 gap is.
    _phase("disagg")
    try:
        from tpulab.disagg import benchmark_disagg
        _record(disagg=benchmark_disagg(
            n_requests=8,
            prompt_len=48,
            steps=8))
    except Exception as e:
        _row_failed("disagg", e)

    # durable token streams (docs/ROBUSTNESS.md "Stream failover
    # semantics"): a chaos mid-stream kill at token N over two loopback
    # replicas, resume-from-delivered ON vs OFF.  The claim tracked: with
    # resume ON the survivor pays one chunked prefill and replayed tokens
    # collapse to zero; OFF re-pays every delivered token.  On CPU jit
    # the replay/prefill counts are the signal; on-device the recovery
    # gap (dead air between last pre-kill and first post-kill token) is.
    _phase("failover_recovery")
    try:
        from tpulab.rpc.replica import benchmark_failover_recovery
        _record(failover_recovery=benchmark_failover_recovery(
            prompt_len=24,
            steps=24,
            kill_at=8))
    except Exception as e:
        _row_failed("failover_recovery", e)

    # fleet prefix-affinity routing (docs/SERVING.md "Fleet routing &
    # autoscaling"): a zipfian multi-tenant trace over >=3 loopback
    # replicas with prefix caches armed, rendezvous affinity ON vs OFF.
    # The claims tracked: fleet-wide prefix-cache hit rate strictly
    # higher with affinity ON (one miss per hot prefix fleet-wide
    # instead of one per replica), no replica starved under the zipf
    # mix, token parity both modes.  On CPU jit the hit-rate/served
    # structure is the signal; on-device the TTFT quantiles are (a
    # prefix hit skips the shared-page prefill on the request path).
    _phase("prefix_affinity")
    try:
        from tpulab.fleet import benchmark_prefix_affinity
        _record(prefix_affinity=benchmark_prefix_affinity(
            n_requests=36,
            steps=6))
    except Exception as e:
        _row_failed("prefix_affinity", e)

    # fleet observability plane (docs/OBSERVABILITY.md "Fleet
    # observability"): the SAME online trace over a 3-replica loopback
    # fleet with the plane armed (FleetObserver fleetz scrapes + event
    # journal) vs off.  The claims tracked: online p99 TTFT/ITL flat
    # within noise armed-vs-off (federation rides the Status/Debug RPCs
    # off the request path), per-scrape wall-clock cost, and the
    # journal's append p99 (one locked write+flush per control-plane
    # decision).
    _phase("fleet_obs")
    try:
        from tpulab.fleet import benchmark_fleet_obs
        _record(fleet_obs=benchmark_fleet_obs(
            n_requests=24,
            steps=6))
    except Exception as e:
        _row_failed("fleet_obs", e)

    # fleet KV fabric (docs/SERVING.md "Fleet KV fabric"): the same
    # 3-replica loopback fleet serving a zipfian trace with routing
    # accuracy GONE (phase 2 round-robins every returning request),
    # fabric ON vs OFF.  The claims tracked: fleet-effective hit rate
    # strictly higher with the fabric ON and above PR 13's ~0.83
    # affinity-working ceiling (astray requests pull the prefix from
    # its home over FetchKV instead of recomputing), token parity
    # between modes, zero stranded requests on degrades.  On CPU jit
    # the hit/pull structure is the signal; on-device the TTFT gap is
    # (a pull replaces a whole prefill on the request path).
    _phase("kv_fabric")
    try:
        from tpulab.kvfabric import benchmark_kv_fabric
        _record(kv_fabric=benchmark_kv_fabric(
            n_requests=24,
            steps=4))
    except Exception as e:
        _row_failed("kv_fabric", e)

    # offline batch lane (docs/SERVING.md "Offline batch lane"): a
    # diurnal online trace — bursts separated by idle valleys — with the
    # preemptible batch lane ON vs OFF.  The claims tracked: total
    # tokens/s strictly higher with the lane on (idle capacity converts
    # to bulk tokens), online p99 TTFT/ITL flat within noise under the
    # SAME online trace, batch preemptions observed (bursts really evict
    # the lane), and the preempted job's output bit-exact vs an
    # uncontended run.
    _phase("batch_soak")
    try:
        from tpulab.batch import benchmark_batch_soak
        _record(batch_soak=benchmark_batch_soak(
            n_cycles=4,
            n_batch_items=24))
    except Exception as e:
        _row_failed("batch_soak", e)

    # admission control under overload (docs/SERVING.md): offer ~2x the
    # measured capacity with per-request deadlines and record goodput
    # (deadline-met completions/s), shed rate, and p99 admission queue
    # wait — admission ON vs OFF on identical load.  The claim tracked:
    # fast-fail + bounded queues convert overload into shed requests
    # instead of deadline-missed (wasted) work.
    _phase("goodput_under_overload")
    try:
        import threading as _th

        import jax.numpy as jnp

        from tpulab.core.deadline import Deadline
        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.transformer import init_transformer_params
        from tpulab.serving import (AdmissionConfig, AdmissionController,
                                    AdmissionRejected)

        ov_params = init_transformer_params(vocab=256, d_model=64,
                                            n_heads=4, n_layers=2, d_ff=256)
        ov_lanes, ov_steps = 4, 16
        ov_n = 32
        ov_rng = np.random.default_rng(0)
        ov_prompts = [ov_rng.integers(0, 256, (8,), np.int32)
                      for _ in range(ov_n + 2 * ov_lanes)]

        def _overload_mode(admission_on: bool) -> dict:
            cb = ContinuousBatcher(ov_params, n_heads=4, n_layers=2,
                                   lanes=ov_lanes, max_len=64, page_size=8,
                                   compute_dtype=jnp.float32)
            try:
                # warm (prefill/decode compiles) FIRST, then measure
                # saturated capacity on a clean batch — compile time in
                # the capacity figure would understate it and turn "2x
                # offered" into under-load
                for f in [cb.submit(p, ov_steps)
                          for p in ov_prompts[ov_n:ov_n + ov_lanes]]:
                    f.result(timeout=300)
                t0 = time.perf_counter()
                for f in [cb.submit(p, ov_steps)
                          for p in ov_prompts[ov_n + ov_lanes:]]:
                    f.result(timeout=300)
                cap_rps = ov_lanes / max(1e-6, time.perf_counter() - t0)
                adm = None
                if admission_on:
                    # tight caps: one lane-set running, half a set queued —
                    # sustained 2x offered load MUST overflow them
                    adm = AdmissionController(AdmissionConfig(
                        max_inflight=ov_lanes,
                        max_queue_depth=max(1, ov_lanes // 2),
                        expected_service_s=ov_lanes / cap_rps), load=cb)
                deadline_s = 2.0 * ov_lanes / cap_rps  # ~2 batches of budget
                interval = 1.0 / (2.0 * cap_rps)       # 2x offered load
                ok, shed, missed, qwaits = [0], [0], [0], []
                lock = _th.Lock()

                def one(i):
                    deadline = Deadline.after(deadline_s)
                    ticket = None
                    try:
                        if adm is not None:
                            ticket = adm.admit(cost=8 + ov_steps,
                                               deadline=deadline)
                            with lock:
                                qwaits.append(ticket.queue_wait_s)
                        cb.submit(ov_prompts[i], ov_steps,
                                  deadline=deadline).result(timeout=300)
                        with lock:
                            ok[0] += 1
                    except AdmissionRejected:
                        with lock:
                            shed[0] += 1
                    except Exception:  # DeadlineExceeded = wasted work
                        with lock:
                            missed[0] += 1
                    finally:
                        if ticket is not None:
                            ticket.release()

                threads = []
                t_start = time.perf_counter()
                for i in range(ov_n):
                    th = _th.Thread(target=one, args=(i,))
                    th.start()
                    threads.append(th)
                    time.sleep(interval)
                for th in threads:
                    th.join(timeout=300)
                wall = max(1e-6, time.perf_counter() - t_start)
                row = {"offered_rps": round(2.0 * cap_rps, 2),
                       "goodput_rps": round(ok[0] / wall, 2),
                       "completed": ok[0], "shed": shed[0],
                       "deadline_missed": missed[0],
                       "shed_rate": round(shed[0] / ov_n, 3)}
                if qwaits:
                    row["queue_wait_ms_p99"] = round(
                        float(np.percentile(qwaits, 99)) * 1e3, 2)
                return row
            finally:
                cb.shutdown()

        _record(goodput_under_overload={
            "n_requests": ov_n, "lanes": ov_lanes, "steps": ov_steps,
            "admission_on": _overload_mode(True),
            "admission_off": _overload_mode(False)})
    except Exception as e:
        _row_failed("goodput", e)

    # flagship serving config (examples/02 analog): gRPC + dynamic batching
    # over localhost (reference 98-series measurement), sieged from a
    # SEPARATE client process (tools/grpc_siege.py, which forces its own
    # JAX to CPU and never touches the chip): a colocated client shares
    # the server's GIL.  The reference's serving numbers are
    # separate-process too (98-series, examples/99).
    _phase("grpc_serving")
    import subprocess

    def _siege(port: int, spec_args: list, timeout_s: float = 600.0) -> dict:
        cmd = [sys.executable,
               os.path.join(REPO, "tools", "grpc_siege.py"),
               "--port", str(port)] + spec_args
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s)
        if proc.returncode != 0:
            raise RuntimeError(f"siege failed: {proc.stderr[-400:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    server = None
    try:
        from tpulab.rpc.executor import Executor as RpcExecutor
        from tpulab.rpc.infer_service import build_infer_service
        # RPC progress threads pinned to their own cpus, clear of the
        # dispatch/transfer threads (reference CQ-thread affinity)
        cpus = sorted(os.sched_getaffinity(0))
        server = build_infer_service(
            mgr, "0.0.0.0:0", batching=True, batch_window_s=0.002,
            executor=RpcExecutor(n_threads=4, contexts_per_thread=64,
                                 cpus=cpus[-4:] if len(cpus) >= 8 else None))
        server.async_start()
        server.wait_until_running()
        rows = _siege(server.bound_port,
                      ["--models", "rn50,rn50i8,echo", "--n", "400",
                       "--depth", "64", "--health", "--health-n", "2000",
                       "--stream-model", "rn50"])
        _record(grpc_client="separate process (deployment shape)")
        # per-row failures are rows too: surface them, don't let a missing
        # key read as "never attempted"
        fails = {k: v for k, v in rows.items()
                 if k.endswith(("_error", "_skipped"))}
        for k, v in fails.items():
            print(f"# siege {k}: {v}", file=sys.stderr)
        if fails:
            _record(grpc_siege_errors=fails)
        if "rn50_inf_s" in rows:
            _record(grpc_batched_b1_inf_s=rows["rn50_inf_s"])
        if "rn50i8_inf_s" in rows:
            _record(grpc_int8_b1_inf_s=rows["rn50i8_inf_s"])
        if "echo_inf_s" in rows:
            # serving path minus compute: with health_rpc_us this splits
            # the rn50 row into machinery / payload / model (VERDICT r4 #2)
            _record(grpc_echo_b1_inf_s=rows["echo_inf_s"])
        if "stream_inf_s" in rows:
            _record(grpc_stream_b1_inf_s=rows["stream_inf_s"])
        if "health_rpc_us" in rows:
            _record(grpc_health_rpc_us=rows["health_rpc_us"])
        # measured per-stage breakdown of the RPC path (where the
        # milliseconds go: aggregation window, pipeline, compute, respond)
        prof = server._infer_resources.stage_profile()
        if prof:
            _record(grpc_stage_profile=prof)
    except Exception as e:
        _row_failed("serving", e)
    finally:  # never leak the server into the rest of the bench
        try:
            if server is not None:
                server.shutdown()  # owns attached service resources
        except Exception as e:
            print(f"# serving teardown: {e!r}", file=sys.stderr)

    # aggregation-window sweep (VERDICT r3 #5: tune the toll with the
    # profiler's evidence): smaller windows cut queue wait, larger ones
    # build bigger groups — measure, don't guess
    _phase("grpc_window_sweep")
    wsweep = {}
    for w in (0.0005, 0.001, 0.004):
        srv2 = None
        try:
            srv2 = build_infer_service(
                mgr, "0.0.0.0:0", batching=True, batch_window_s=w)
            srv2.async_start()
            srv2.wait_until_running()
            rows = _siege(srv2.bound_port,
                          ["--models", "rn50", "--n", "200",
                           "--depth", "64"])
            wsweep[f"{w * 1e3:g}ms"] = rows.get("rn50_inf_s", 0.0)
        except Exception as e:
            _row_failed(f"grpc_window_{w * 1e3:g}ms", e)
        finally:
            if srv2 is not None:
                srv2.shutdown()
    _record(grpc_window_sweep=wsweep)

    # speculative decoding's reason to exist, measured ON THE SERVING
    # PATH (ROADMAP item 4): acceptance rate, tok/s, and
    # tokens-per-dispatch of speculative decode blocks vs plain K-blocks
    # through the SAME ContinuousBatcher workload, greedy parity
    # recorded in the row (the decode_dispatch discipline).  Supersedes
    # the dense-path `speculative` row — benchmark_speculative_decode
    # owns the plain baseline both modes share, so there is no
    # duplicated baseline loop.
    try:
        _phase("speculative_decode")
        from tpulab.engine.paged import benchmark_speculative_decode
        _record(speculative_decode=benchmark_speculative_decode(steps=48))
    except Exception as e:
        _row_failed("speculative", e)

    # ragged dispatch plan (docs/PERFORMANCE.md "Ragged paged
    # attention"): one fused mixed prefill+decode program vs the legacy
    # split dispatch across batch-raggedness shapes, kernel mode included.
    try:
        _phase("ragged_attention")
        from tpulab.engine.paged import benchmark_ragged_attention
        # 4 heads of 128: the helper's toy default (4 heads of 16) is a
        # 64-lane page row, which the kernel's shape rule excludes on the
        # chip (ops/ragged_attention.kernel_geometry_error)
        _record(ragged_attention=benchmark_ragged_attention(
            kernel=True, d_model=512, n_heads=4))
    except Exception as e:
        _row_failed("ragged_attention", e)

    _phase("emit")
    _emit_line(device)
    mgr.shutdown()
    mgr_b1.shutdown()
    return 1 if _failed_rows else 0


if __name__ == "__main__":
    sys.exit(main())
