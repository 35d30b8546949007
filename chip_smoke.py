#!/usr/bin/env python
"""Does tpulab's main path still start on the chip?

One command, one process holding the chip, real widths, a few requests::

    python chip_smoke.py            # on a TPU host (here: chiprun -- python chip_smoke.py)

Phases, in order; any phase that raises fails the run (exit 1):

1. device   — ``jax.devices()[0].platform == "tpu"`` or exit 2 before
              anything is built; Pallas interpret mode must be off.
   native   — the C++ host core is built from ``cpp/`` into a temporary
              directory and loaded from there (nothing git would not
              commit is used, nothing is left in the tree).
2. rn50     — ``build_model("resnet50", max_batch_size=128, uint8)`` →
              ``InferenceManager`` → ``serve(batching=True)`` →
              ``RemoteInferenceManager`` over localhost gRPC at b=1, 8, 128;
              logits must match a direct ``runner.infer`` of the same input.
3. lm       — the paged LM (hidden 2048, 16Q/4KV heads of 128, bf16,
              page 16, vocab 50304, depth cut) behind the Generate RPC,
              concurrent streams through the XLA gather and through the
              kernels (one dispatch plan: prompts ride mixed rounds).
   latent   — a two-layer MLA + expert model (GLM-4.7-Flash's widths, one
              dense and one expert layer, 64 experts top-4 + shared) on a
              latent page store: a mixed round and a decode step through
              the latent ragged kernel against the XLA gather, the logit
              error printed.
   jamba    — a three-layer Mamba/attention hybrid (AI21-Jamba2-3B's
              widths: Mamba, attention, Mamba) on a lane-state store beside
              the page store: three mixed rounds and a decode step through the
              ``selective_scan`` and ragged kernels against the XLA forms,
              logits and lane state.
   keye_vl2 — a two-layer GQA decoder with a learned indexer and 128
              softmax-routed experts (Keye-VL-2.0-30B-A3B's widths and
              ``sa_config``) on K/V pages with index rows beside them:
              rounds that take one lane past ``topk`` 2,048 keys, a mixed
              round and a decode step through the score, sparse-attention
              and decode kernels against the XLA forms, logits and index
              rows.
   qwen3_next — a two-layer Gated DeltaNet / gated-attention hybrid
              (Qwen3-Next-80B-A3B's widths: a 128 x 128 float32 state a head
              a lane, 16/2 heads of 256 with RoPE over 64, a share of 32 of
              128 softmax-routed experts at top-10 and a gated shared expert)
              on a lane-state store filled with junk: three mixed rounds
              and a decode step through the ``chunk_gated_delta_rule``,
              ``gated_delta_step`` and ragged kernels against the XLA forms,
              logits and lane state.
   evabyte  — a two-layer EvaByte (its published widths: 32 heads on 32 KV
              heads of 128, SwiGLU 11008, windows of 2,048 bytes in chunks
              of 16, 320 rows, eight prediction heads) through
              ``ContinuousBatcher``: a prompt across two window boundaries
              (two compactions in rounds, the ``eva_chunk_summary`` kernel),
              then decode across a third, against the plain float32
              reference (``perf/reference/evabyte.py``).
4. kernels  — the ragged Pallas kernels compiled by Mosaic
              (``interpret=False``, custom call present in the lowered
              program) against the XLA gather.
5. multichip— with more than one device: one RN50 replica per chip through
              ``MultiDeviceDispatcher`` and the LM on a ``{"model": N}``
              mesh against the single-device logits.

The last line of stdout is ``{"ok": true, "device": {...}}`` and the exit
code 0 only when every phase passed on a TPU.  The gRPC clients are threads
of this process: nothing else ever needs the chip.

``--rehearse-cpu`` walks the same code at toy sizes on the CPU backend, for
debugging control flow before spending chip time.  It labels every line,
prints no result line and exits 3: a rehearsal is never a pass.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.abspath(__file__))
# the run leaves nothing in the tree, not even bytecode caches
sys.dont_write_bytecode = True

EXIT_NO_CHIP = 2
EXIT_REHEARSAL = 3
#: the run must end well inside the driver's 1200 s; past this every
#: thread's stack goes to stderr and the process exits 1
DEADLINE_S = 1100

#: attention outputs of a bf16 kernel against the XLA reference on the same
#: bf16 inputs (f32 accumulation both sides; bf16 rounds at ~4e-3 relative)
ATTN_TOL = 2e-2
#: logits of the same request through two programs (different reduction
#: order, bf16 activations), relative to the largest logit
LOGIT_RTOL = 5e-2
#: of the 64 expert assignments of phase keye_vl2's decode step (4 rows,
#: top-8, 2 layers), how many the two forms may make differently: a
#: router's near-tie falls either way under bf16 activations
KEYE_FLIPS = 2


@dataclass
class Sizes:
    """What each phase builds: the real widths, or the rehearsal's toys."""
    rn50_kwargs: dict = field(default_factory=lambda: dict(
        max_batch_size=128))
    rn50_buckets: tuple = (1, 2, 4, 8, 16, 32, 64, 128)
    rn50_batches: tuple = (1, 8, 1, 8, 128)
    # the dense GQA + RoPE + RMSNorm + SwiGLU block the engine serves, at
    # ROADMAP R1's attention geometry; depth is the only cut
    lm: dict = field(default_factory=lambda: dict(
        vocab=50304, d_model=2048, n_heads=16, n_kv_heads=4, n_layers=4,
        d_ff=5632))
    # GLM-4.7-Flash's published widths (perf/configs/glm47flash-l8.json);
    # depth and vocabulary are the cuts
    glm: dict = field(default_factory=lambda: dict(
        hidden_size=2048, intermediate_size=10240, num_attention_heads=20,
        num_hidden_layers=2, first_k_dense_replace=1, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, n_routed_experts=64, num_experts_per_tok=4,
        n_shared_experts=1, moe_intermediate_size=1536,
        routed_scaling_factor=1.8, norm_topk_prob=True, rms_norm_eps=1e-5,
        rope_theta=1e6, vocab_size=50304))
    glm_chunk: int = 256
    # AI21-Jamba2-3B's published widths (perf/configs/jamba2-3b.json);
    # depth (one period of a shorter pattern) and vocabulary are the cuts
    jamba: dict = field(default_factory=lambda: dict(
        hidden_size=2560, intermediate_size=8192, num_attention_heads=20,
        num_key_value_heads=1, num_hidden_layers=3, attn_layer_period=3,
        attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4,
        mamba_dt_rank=160, mamba_expand=2, num_experts=1,
        rms_norm_eps=1e-6, vocab_size=50304))
    # Keye-VL-2.0-30B-A3B's published widths and sa_config
    # (perf/configs/keyevl2-l6.json); depth and vocabulary are the cuts
    keye: dict = field(default_factory=lambda: dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, num_hidden_layers=2, num_experts=128,
        num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e7,
        sa_config=dict(indexer_head_dim=64, indexer_num_heads=16,
                       indexer_num_kv_heads=1, topk=2048),
        vocab_size=50304))
    # Qwen3-Next-80B-A3B's published widths
    # (perf/configs/qwen3next-l8-ep4.json); depth (one period of a shorter
    # pattern), the router's width and the vocabulary are the cuts; this
    # model holds experts 32 .. 64 of its router's 128
    qwen3_next: dict = field(default_factory=lambda: dict(
        hidden_size=2048, num_attention_heads=16, num_key_value_heads=2,
        head_dim=256, partial_rotary_factor=0.25, num_hidden_layers=2,
        full_attention_interval=2, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4, num_experts=128,
        num_experts_per_tok=10, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=1e7, vocab_size=50304))
    qwen3_next_share: tuple = (32, 32)     # first, held
    # EvaByte's published widths (perf/configs/evabyte-l8.json); depth is
    # the cut
    evabyte: dict = field(default_factory=lambda: dict(
        model_type="evabyte", attention_class="eva", hidden_size=4096,
        intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, num_hidden_layers=2, num_pred_heads=8,
        window_size=2048, chunk_size=16, rms_norm_eps=1e-5, rope_theta=1e5,
        vocab_size=320))
    lm_max_len: int = 512
    lm_page_size: int = 16
    lm_prefill_chunk: int = 128
    lm_prompt_lens: tuple = (8, 50, 300)   # one longer than prefill_chunk
    lm_steps: int = 24


REHEARSAL_SIZES = Sizes(
    rn50_kwargs=dict(max_batch_size=8, image_size=32, num_classes=16),
    rn50_buckets=(1, 2, 4, 8),
    rn50_batches=(1, 8, 1),
    lm=dict(vocab=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=128),
    glm=dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_hidden_layers=2, first_k_dense_replace=1, q_lora_rank=24,
             kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8,
             v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
             n_shared_experts=1, moe_intermediate_size=48,
             routed_scaling_factor=1.8, norm_topk_prob=True,
             rms_norm_eps=1e-5, rope_theta=1e6, vocab_size=256),
    glm_chunk=16,
    jamba=dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=1, num_hidden_layers=3,
               attn_layer_period=3, attn_layer_offset=1, mamba_d_state=8,
               mamba_d_conv=4, mamba_dt_rank=6, mamba_expand=2,
               num_experts=1, rms_norm_eps=1e-6, vocab_size=256),
    keye=dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
              head_dim=32, num_hidden_layers=2, num_experts=8,
              num_experts_per_tok=2, moe_intermediate_size=48,
              norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e4,
              sa_config=dict(indexer_head_dim=16, indexer_num_heads=4,
                             indexer_num_kv_heads=1, topk=24),
              vocab_size=256),
    qwen3_next=dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, partial_rotary_factor=0.25, num_hidden_layers=2,
        full_attention_interval=2, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=1e4, vocab_size=256),
    qwen3_next_share=(4, 4),
    evabyte=dict(
        model_type="evabyte", attention_class="eva", hidden_size=64,
        intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
        num_hidden_layers=2, num_pred_heads=3, window_size=64, chunk_size=8,
        rms_norm_eps=1e-5, rope_theta=1e5, vocab_size=64),
    lm_max_len=96, lm_page_size=8, lm_prefill_chunk=16,
    lm_prompt_lens=(5, 12, 40), lm_steps=6)


class Smoke:
    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.sizes = REHEARSAL_SIZES if rehearsal else Sizes()
        self.stamp = ""

    def say(self, msg: str) -> None:
        tag = "REHEARSAL(cpu, not a pass) " if self.rehearsal else ""
        print(f"{tag}{msg} | {self.stamp}", flush=True)

    def run(self, name: str, phase) -> None:
        t0 = time.monotonic()
        detail = phase(self)
        self.say(f"phase {name}: ok ({time.monotonic() - t0:.0f}s) {detail}")

    def rn50(self, **overrides):
        """The RN50 servable at this run's sizes (uint8 images in)."""
        import numpy as np

        from tpulab.models import build_model
        return build_model("resnet50", **dict(
            self.sizes.rn50_kwargs, input_dtype=np.uint8, **overrides))


# -- phase 1: device + native host core --------------------------------------
def build_native_core(tmp: str) -> str:
    """cmake + ninja ``cpp/`` into ``tmp``; returns the library path.  The
    children are compilers: they never touch JAX or the chip."""
    for cmd in (["cmake", "-S", os.path.join(REPO, "cpp"), "-B", tmp,
                 "-G", "Ninja"],
                ["ninja", "-C", tmp, "tpulab_native"]):
        subprocess.run(cmd, check=True, stdout=subprocess.DEVNULL,
                       timeout=300)
    return os.path.join(tmp, "libtpulab_native.so")


# -- phase 2: RN50 through the serving entry points --------------------------
def phase_rn50(smoke: Smoke) -> str:
    import numpy as np

    import tpulab

    sz = smoke.sizes
    model = smoke.rn50()
    spec, out_spec = model.inputs[0], model.outputs[0]
    manager = tpulab.InferenceManager(max_exec_concurrency=4)
    remote = None
    try:
        manager.register_model("rn50", model)
        manager.update_resources()
        compiled = sorted(manager.compiled("rn50").executables)
        if compiled != list(sz.rn50_buckets):
            raise AssertionError(f"compiled buckets {compiled}, expected "
                                 f"{list(sz.rn50_buckets)}")
        manager.serve(port=0, batching=True)
        remote = tpulab.RemoteInferenceManager(
            f"localhost:{manager.server.bound_port}")
        served = remote.get_models()
        if "rn50" not in served:
            raise AssertionError(f"server lists {sorted(served)}")
        rrunner = remote.infer_runner("rn50")
        lrunner = manager.infer_runner("rn50")
        rng = np.random.default_rng(0)
        worst = 0.0
        for b in sz.rn50_batches:
            x = rng.integers(0, 256, (b, *spec.shape)).astype(spec.np_dtype)
            got = rrunner.infer(**{spec.name: x}).result(timeout=300)
            want = lrunner.infer(**{spec.name: x}).result(timeout=300)
            g, w = got[out_spec.name], want[out_spec.name]
            if g.shape != (b, *out_spec.shape) or g.dtype != w.dtype:
                raise AssertionError(
                    f"b={b}: got {g.shape} {g.dtype}, direct runner gave "
                    f"{w.shape} {w.dtype}")
            if not np.isfinite(g).all():
                raise AssertionError(f"b={b}: non-finite logits over gRPC")
            # the batching server may run the request in another bucket
            # than the direct runner: bf16 conv reduction order differs
            err = float(np.abs(g.astype(np.float32) - w.astype(np.float32))
                        .max() / max(1.0, float(np.abs(w).max())))
            worst = max(worst, err)
            if err > LOGIT_RTOL:
                raise AssertionError(
                    f"b={b}: gRPC logits differ from the direct runner by "
                    f"{err:.3g} (limit {LOGIT_RTOL})")
        return (f"buckets={compiled} grpc_batches={list(sz.rn50_batches)} "
                f"max_rel_err_vs_direct={worst:.2g}")
    finally:
        if remote is not None:
            remote.close()
        manager.shutdown()


# -- phase 3: the paged LM through the Generate RPC --------------------------
#: the two attentions the engine's one dispatch plan runs through
#: (ContinuousBatcher options)
LM_PLANS = (
    ("lm_gather", dict(use_kernel=False)),
    # un-chunked: the long prompt rides the widest mixed round
    # (RAGGED_CHUNK_CAP) through the kernel
    ("lm_kernel", dict(use_kernel=True, prefill_chunk=None)),
)


def lm_params(smoke: Smoke):
    import jax
    import jax.numpy as jnp

    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(seed=0, ffn="swiglu",
                                     tie_embeddings=False, **smoke.sizes.lm)
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)


def lm_engine(smoke: Smoke, params, mesh=None, **plan):
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    sz = smoke.sizes
    kw = dict(prefill_chunk=sz.lm_prefill_chunk)
    kw.update(plan)
    return ContinuousBatcher(
        params, n_heads=sz.lm["n_heads"], n_layers=sz.lm["n_layers"],
        n_kv_heads=sz.lm["n_kv_heads"], lanes=4, max_len=sz.lm_max_len,
        page_size=sz.lm_page_size, compute_dtype=jnp.bfloat16,
        rope_theta=10000.0, mesh=mesh, **kw)


def stream_generations(smoke: Smoke, remote, model_name: str) -> int:
    """Concurrent streamed generations against one served engine: mixed
    prompt lengths, the last one seeded and device-sampled.  Returns the
    number of tokens streamed; raises on a wrong count, an id out of
    range, or a stream that does not end."""
    import numpy as np

    from tpulab.rpc.infer_service import GenerateStreamClient
    sz = smoke.sizes
    vocab, steps = sz.lm["vocab"], sz.lm_steps
    rng = np.random.default_rng(1)
    jobs = [(rng.integers(0, vocab, (n,)).astype(np.int32), {})
            for n in sz.lm_prompt_lens]
    jobs.append((jobs[0][0], dict(temperature=0.8, seed=7,
                                  device_sampling=True)))
    results: list = [None] * len(jobs)

    def one(i: int) -> None:
        prompt, kw = jobs[i]
        try:
            client = GenerateStreamClient(remote, model_name)
            results[i] = list(client.generate(prompt, steps, timeout=600,
                                              **kw))
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    for i, (t, res) in enumerate(zip(threads, results)):
        if t.is_alive():
            raise AssertionError(f"{model_name}: stream {i} did not end")
        if isinstance(res, BaseException):
            raise res
        if len(res) != steps:
            raise AssertionError(
                f"{model_name}: stream {i} gave {len(res)} tokens, "
                f"asked for {steps}")
        if not all(0 <= tok < vocab for tok in res):
            raise AssertionError(f"{model_name}: stream {i} token id out "
                                 f"of [0, {vocab})")
    return steps * len(jobs)


def phase_lm(smoke: Smoke) -> str:
    import tpulab

    params = lm_params(smoke)
    engines = {}
    manager = tpulab.InferenceManager(max_exec_concurrency=1)
    remote = None
    try:
        for name, plan in LM_PLANS:
            cb = engines[name] = lm_engine(smoke, params, **plan)
            if cb.use_kernel != plan["use_kernel"]:
                raise AssertionError(
                    f"plan {name}: asked use_kernel={plan['use_kernel']}, "
                    f"engine selected {cb.use_kernel}")
        manager.serve(port=0, generation_engines=engines)
        remote = tpulab.RemoteInferenceManager(
            f"localhost:{manager.server.bound_port}")
        report = []
        for name, _ in LM_PLANS:
            n = stream_generations(smoke, remote, name)
            cb = engines[name]
            report.append(f"{name}: {n} tokens, dispatches="
                          f"{dict(cb.dispatch_kinds)}")
            kinds = cb.dispatch_kinds
            if not (kinds["mixed"] and kinds["decode"]):
                raise AssertionError(f"plan {name} ran no mixed round or no "
                                     f"decode block: {kinds}")
        return "; ".join(report)
    finally:
        if remote is not None:
            remote.close()
        manager.shutdown()
        for cb in engines.values():
            cb.shutdown()


# -- phase 3b: a latent cache entry and expert layers --------------------------
def phase_latent(smoke: Smoke) -> str:
    """One mixed round (a prompt chunk beside decode lanes) and one decode
    step of a two-layer MLA + expert model over a latent page store, by the
    latent ragged kernel and by the XLA gather, on the same inputs."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.kv_pool import PagedKVPool
    from tpulab.engine.paged_steps import (moe_shape, pack_round,
                                           paged_decode_step,
                                           paged_mixed_step,
                                           paged_ragged_forward,
                                           result_fields, unpack_words)
    from tpulab.models.spec import glm4_moe_lite_spec, init_params
    sz = smoke.sizes
    cfg, chunk, page = sz.glm, sz.glm_chunk, sz.lm_page_size
    spec = glm4_moe_lite_spec(cfg)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        init_params(spec, cfg["vocab_size"], cfg["intermediate_size"]))
    lanes, mp = 4, 3 * chunk // page
    rng = np.random.default_rng(2)
    tables = np.zeros((lanes, mp), np.int32)
    tables[:3] = 1 + np.arange(3 * mp).reshape(3, mp)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    kw = dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
              compute_dtype=jnp.bfloat16, spec=spec)
    # three contexts go in through the padded form; then the round, packed
    # by token: lane 0 the second chunk of its prompt, lanes 1, 2 decode,
    # lane 3 idle
    fill = (i32(rng.integers(0, cfg["vocab_size"], (lanes, chunk))),
            i32([chunk, chunk - 3, chunk // 2, 0]))
    kv_lens = i32([2 * chunk, chunk - 2, chunk // 2 + 1, 0])
    packed = round_buffer(tables, *pack_round(
        lanes, {0: rng.integers(0, cfg["vocab_size"], chunk)},
        {1: int(rng.integers(cfg["vocab_size"])),
         2: int(rng.integers(cfg["vocab_size"]))}), kv_lens)
    round_kw = dict(kw, lanes=lanes, max_pages=mp)
    out = {}
    for name, uk in (("gather", False), ("kernel", True)):
        pool = PagedKVPool(3 * mp + 1, page, spec.n_layers, 0, 0,
                           jnp.bfloat16, latent_width=spec.latent_width)
        mixed = jax.jit(partial(paged_mixed_step, use_kernel=uk, **round_kw),
                        donate_argnums=(1,))
        padded = jax.jit(partial(paged_ragged_forward, use_kernel=uk,
                                 last_only=True, **kw), donate_argnums=(1,))
        step = jax.jit(partial(paged_decode_step, use_kernel=uk, **kw),
                       donate_argnums=(1,))
        if uk:
            check_mosaic(smoke, "latent mixed round", partial(
                paged_mixed_step, use_kernel=True, **round_kw), params,
                pool.kv, packed, no_carry(lanes))
        _, kv, _ = padded(params, pool.kv, i32(tables), *fill, fill[1])
        res, last, *_carry, kv = mixed(params, kv, packed, no_carry(lanes))
        moe = unpack_words(result_fields(lanes, moe=moe_shape(spec)),
                           np.asarray(res))["moe"]
        logits, kv, _ = step(params, kv, i32(tables), kv_lens,
                             i32([5, 6, 7, 0]),
                             jnp.asarray([True, True, True, False]))
        out[name] = (np.asarray(last, np.float32)[:3],
                     np.asarray(logits, np.float32)[:3], np.asarray(moe))
        pool.close()
    report = []
    for i, what in enumerate(("mixed round", "decode step")):
        ref, got = out["gather"][i], out["kernel"][i]
        err = float(np.abs(got - ref).max())
        scale = float(np.abs(ref).max())
        if not np.isfinite(got).all() or err > LOGIT_RTOL * scale:
            raise AssertionError(f"latent {what}: kernel logits {err:.4g} "
                                 f"from the gather's (largest {scale:.4g})")
        report.append(f"{what} logit err {err:.4g} of {scale:.4g}")
    counts = out["kernel"][2][:, :spec.n_experts]
    if counts.sum() != spec.top_k * (chunk + 2):
        raise AssertionError(f"expert assignments {counts.sum()} != top-"
                             f"{spec.top_k} x {chunk + 2} valid rows")
    return "; ".join(report) + (f"; {int((counts > 0).sum())} of "
                                f"{spec.n_experts} experts hit")


# -- phase 3c: Mamba layers on a per-lane state -------------------------------
def phase_jamba(smoke: Smoke) -> str:
    """Three mixed rounds and a decode step of a Mamba / attention / Mamba
    model over a lane-state store filled with junk (a reused lane): the
    ``selective_scan`` and ragged kernels against the ``lax.scan`` form and
    the XLA gather on the same inputs, logits and lane state."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.kv_pool import PagedKVPool, lane_state_shapes
    from tpulab.engine.paged_steps import (pack_round, paged_decode_step,
                                           paged_mixed_step)
    from tpulab.models.spec import init_params, jamba_spec
    sz = smoke.sizes
    cfg, chunk, page = sz.jamba, sz.glm_chunk, sz.lm_page_size
    spec = jamba_spec(cfg)
    vocab = cfg["vocab_size"]
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        init_params(spec, vocab, cfg["intermediate_size"]))
    lanes, mp = 4, 3 * chunk // page
    rng = np.random.default_rng(2)
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    kw = dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
              compute_dtype=jnp.bfloat16, spec=spec)
    draw = lambda n: rng.integers(0, vocab, n)
    # rounds 1, 2: three lanes' first chunks (a round carries at most
    # ``chunk`` prompt tokens, the engine's budget); round 3: lane 0 its
    # second chunk, lanes 1, 2 decode, lane 3 a first chunk of 7 into a slot
    # that holds junk; then a decode step of all four
    half = chunk // 2
    rounds = [({0: draw(chunk)}, {}, [0, 0, 0, 0]),
              ({1: draw(half - 3), 2: draw(half)}, {}, [chunk, 0, 0, 0]),
              ({0: draw(chunk - 8), 3: draw(7)},
               {1: int(draw(1)[0]), 2: int(draw(1)[0])},
               [chunk, half - 3, half, 0])]
    final = [2 * chunk - 8, half - 2, half + 1, 7]
    out = {}
    for name, uk in (("xla", False), ("kernel", True)):
        pool = PagedKVPool(lanes * mp + 1, page, len(spec.attention_layers),
                           spec.n_kv_heads, spec.head_dim, jnp.bfloat16)
        store = (pool.kv, tuple(jnp.full(shape, 3, dtype) for shape, dtype
                                in lane_state_shapes(spec, lanes,
                                                     jnp.bfloat16)))
        mixed = jax.jit(partial(paged_mixed_step, use_kernel=uk, lanes=lanes,
                                max_pages=mp, **kw), donate_argnums=(1,))
        step = jax.jit(partial(paged_decode_step, use_kernel=uk, **kw),
                       donate_argnums=(1,))
        for prefill, decode, lengths in rounds:
            toks, row_lane, row_off, q_lens = pack_round(lanes, prefill,
                                                         decode)
            packed = round_buffer(tables, toks, row_lane, row_off, q_lens,
                                  np.asarray(lengths) + q_lens)
            if uk and decode:
                check_mosaic(smoke, "jamba mixed round", partial(
                    paged_mixed_step, use_kernel=True, lanes=lanes,
                    max_pages=mp, **kw), params, store, packed,
                    no_carry(lanes))
            _, last, *_carry, store = mixed(params, store, packed,
                                            no_carry(lanes))
        logits, store = step(params, store, i32(tables), i32(final),
                             i32([5, 6, 7, 8]), jnp.ones((lanes,), bool))
        out[name] = (np.asarray(last, np.float32),
                     np.asarray(logits, np.float32),
                     np.asarray(store[1][0]))
        pool.close()
    report = []
    for i, what in enumerate(("mixed round", "decode step", "ssm state")):
        ref, got = out["xla"][i], out["kernel"][i]
        err = float(np.abs(got - ref).max())
        scale = float(np.abs(ref).max())
        if not np.isfinite(got).all() or err > LOGIT_RTOL * scale:
            raise AssertionError(f"jamba {what}: with the kernels {err:.4g} "
                                 f"from the XLA forms (largest {scale:.4g})")
        report.append(f"{what} err {err:.4g} of {scale:.4g}")
    return "; ".join(report)


def phase_keye(smoke: Smoke) -> str:
    """Rounds that fill lane 0 past ``topk`` keys, one mixed round (lane 0 a
    later chunk whose every row drops keys, lanes 1 and 2 decode, lane 3 a
    first chunk) and a decode step of a two-layer model with a learned
    indexer: the score, sparse-attention and decode kernels against the
    XLA forms on the same inputs, logits and index rows, each path on the
    store its own rounds filled (what production runs).  The decode step
    also returns its expert counters: a row whose router scores nearly tie
    may take another expert under one form than under the other (seen on
    the chip at PR 35: one of the step's 64 assignments, in lane 0, and
    that lane 0.33 of 4.41 from the XLA form where the other three read
    0.027-0.032), so a lane may pass ``LOGIT_RTOL`` only as an assignment
    that differs allows: at most ``KEYE_FLIPS`` of them, a lane each, and
    such a lane within a ``top_k``-th of the scale (the expert that changes
    is the least of its row's ``top_k``)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.kv_pool import PagedKVPool
    from tpulab.engine.paged_steps import (pack_round, paged_decode_step,
                                           paged_mixed_step)
    from tpulab.models.spec import init_params, keye_vl2_spec
    sz = smoke.sizes
    cfg, chunk, page = sz.keye, sz.glm_chunk, sz.lm_page_size
    spec = keye_vl2_spec(cfg)
    vocab = cfg["vocab_size"]
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                    init_params(spec, vocab, 0))
    fills = -(-spec.index_topk // chunk) + 1     # lane 0 ends past topk
    lanes, mp = 4, (fills + 2) * chunk // page
    rng = np.random.default_rng(3)
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    kw = dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
              compute_dtype=jnp.bfloat16, spec=spec)
    draw = lambda n: rng.integers(0, vocab, n)
    half = chunk // 2
    rounds = [({0: draw(chunk)}, {}, [i * chunk, 0, 0, 0])
              for i in range(fills)]
    rounds += [({1: draw(half - 3), 2: draw(half)}, {},
                [fills * chunk, 0, 0, 0]),
               ({0: draw(chunk - 8), 3: draw(7)},
                {1: int(draw(1)[0]), 2: int(draw(1)[0])},
                [fills * chunk, half - 3, half, 0])]
    final = [(fills + 1) * chunk - 8, half - 2, half + 1, 7]
    out, counts = {}, {}
    for name, uk in (("xla", False), ("kernel", True)):
        pool = PagedKVPool(lanes * mp + 1, page, spec.n_layers,
                           spec.n_kv_heads, spec.head_dim, jnp.bfloat16,
                           index_dim=spec.index_dim)
        store = (pool.kv, pool.index)
        mixed = jax.jit(partial(paged_mixed_step, use_kernel=uk, lanes=lanes,
                                max_pages=mp, **kw), donate_argnums=(1,))
        step = jax.jit(partial(paged_decode_step, use_kernel=uk, **kw),
                       donate_argnums=(1,))
        for prefill, decode, lengths in rounds:
            toks, row_lane, row_off, q_lens = pack_round(lanes, prefill,
                                                         decode)
            packed = round_buffer(tables, toks, row_lane, row_off, q_lens,
                                  np.asarray(lengths) + q_lens)
            if uk and decode:
                check_mosaic(smoke, "keye_vl2 mixed round", partial(
                    paged_mixed_step, use_kernel=True, lanes=lanes,
                    max_pages=mp, **kw), params, store, packed,
                    no_carry(lanes))
            _, last, *_carry, store = mixed(params, store, packed,
                                            no_carry(lanes))
        logits, store, experts = step(params, store, i32(tables), i32(final),
                                      i32([5, 6, 7, 8]),
                                      jnp.ones((lanes,), bool))
        out[name] = (np.asarray(last, np.float32),
                     np.asarray(logits, np.float32),
                     np.asarray(store[1][0, 1:], np.float32))
        # the step's assignments per expert, a layer
        counts[name] = np.asarray(experts)[:, :-2]
    flips = int(np.abs(counts["kernel"] - counts["xla"]).sum()) // 2
    if flips > KEYE_FLIPS:
        raise AssertionError(f"keye_vl2 decode step: {flips} of "
                             f"{int(counts['xla'].sum())} expert "
                             f"assignments differ between the forms "
                             f"(limit {KEYE_FLIPS})")
    report = []
    for i, what in enumerate(("mixed round", "decode step", "index rows")):
        ref, got = out["xla"][i], out["kernel"][i]
        scale = float(np.abs(ref).max())
        by_row = np.abs(got - ref).reshape(len(ref), -1).max(axis=1)
        over = by_row > LOGIT_RTOL * scale
        # only the decode step's lanes may lean on a flipped assignment
        allowed = flips if what == "decode step" else 0
        if (not np.isfinite(got).all() or over.sum() > allowed
                or by_row.max() > scale / spec.top_k):
            raise AssertionError(
                f"keye_vl2 {what}: with the kernels "
                f"{float(by_row.max()):.4g} from the XLA forms (largest "
                f"{scale:.4g}; by row {np.round(by_row, 4).tolist()}; "
                f"{flips} expert assignments differ)")
        report.append(f"{what} err {float(by_row[~over].max()):.4g} of "
                      f"{scale:.4g}" + (
                          f" ({int(over.sum())} lane after another expert: "
                          f"{float(by_row.max()):.4g})" if over.any()
                          else ""))
    return (f"lane 0 at {final[0] + 1} keys of topk {spec.index_topk}; "
            + "; ".join(report) + f"; {flips} of "
            f"{int(counts['xla'].sum())} decode assignments differ")


# -- phase 3e: Gated DeltaNet layers on a matrix-valued lane state -------------
def phase_qwen3_next(smoke: Smoke) -> str:
    """Three mixed rounds and a decode step of a Gated DeltaNet / gated
    attention model that holds a share of its experts, over a lane-state
    store filled with junk (a reused lane): the ``chunk_gated_delta_rule``,
    ``gated_delta_step`` (the decode step's lanes, the round's decode rows)
    and ragged kernels against the ``lax.scan`` form, the XLA one-token rule
    and the XLA gather on the same inputs, logits and lane state.  As in phase ``keye_vl2`` a
    router's near tie may fall either way under the two forms (the
    attention layer's rows differ by bf16 rounding), so the decode step's
    expert counters say how many assignments differ, and only so many lanes
    may pass ``LOGIT_RTOL``."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.kv_pool import PagedKVPool, lane_state_shapes
    from tpulab.engine.paged_steps import (pack_round, paged_decode_step,
                                           paged_mixed_step)
    from tpulab.models.spec import init_params, qwen3_next_spec
    sz = smoke.sizes
    cfg, chunk, page = sz.qwen3_next, sz.glm_chunk, sz.lm_page_size
    first, held = sz.qwen3_next_share
    spec = qwen3_next_spec(cfg, first=first, held=held)
    vocab = cfg["vocab_size"]
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                    init_params(spec, vocab, 0))
    lanes, mp = 4, 3 * chunk // page
    rng = np.random.default_rng(4)
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    kw = dict(n_heads=spec.n_heads, n_layers=spec.n_layers,
              compute_dtype=jnp.bfloat16, spec=spec)
    draw = lambda n: rng.integers(0, vocab, n)
    # the rounds of phase ``jamba``: two that fill three lanes, then lane 0
    # its second chunk (four passes of the chunk kernel from the slot), lanes
    # 1 and 2 decode, lane 3 a first chunk of 7 (a pass that shares lane 0's
    # last chunk of rows) into a slot that holds junk; then a decode step
    half = chunk // 2
    rounds = [({0: draw(chunk)}, {}, [0, 0, 0, 0]),
              ({1: draw(half - 3), 2: draw(half)}, {}, [chunk, 0, 0, 0]),
              ({0: draw(chunk - 8), 3: draw(7)},
               {1: int(draw(1)[0]), 2: int(draw(1)[0])},
               [chunk, half - 3, half, 0])]
    final = [2 * chunk - 8, half - 2, half + 1, 7]
    out, counts = {}, {}
    for name, uk in (("xla", False), ("kernel", True)):
        pool = PagedKVPool(lanes * mp + 1, page, len(spec.attention_layers),
                           spec.n_kv_heads, spec.head_dim, jnp.bfloat16)
        store = (pool.kv, tuple(jnp.full(shape, 3, dtype) for shape, dtype
                                in lane_state_shapes(spec, lanes,
                                                     jnp.bfloat16)))
        mixed = jax.jit(partial(paged_mixed_step, use_kernel=uk, lanes=lanes,
                                max_pages=mp, **kw), donate_argnums=(1,))
        step = jax.jit(partial(paged_decode_step, use_kernel=uk, **kw),
                       donate_argnums=(1,))
        for prefill, decode, lengths in rounds:
            toks, row_lane, row_off, q_lens = pack_round(lanes, prefill,
                                                         decode)
            packed = round_buffer(tables, toks, row_lane, row_off, q_lens,
                                  np.asarray(lengths) + q_lens)
            if uk and decode:
                check_mosaic(smoke, "qwen3_next mixed round", partial(
                    paged_mixed_step, use_kernel=True, lanes=lanes,
                    max_pages=mp, **kw), params, store, packed,
                    no_carry(lanes))
            _, last, *_carry, store = mixed(params, store, packed,
                                            no_carry(lanes))
        logits, store, experts = step(params, store, i32(tables), i32(final),
                                      i32([5, 6, 7, 8]),
                                      jnp.ones((lanes,), bool))
        out[name] = (np.asarray(last, np.float32),
                     np.asarray(logits, np.float32),
                     np.asarray(store[1][0]).reshape(lanes, -1))
        counts[name] = np.asarray(experts)[:, :-2]
    flips = int(np.abs(counts["kernel"] - counts["xla"]).sum()) // 2
    if flips > KEYE_FLIPS:
        raise AssertionError(f"qwen3_next decode step: {flips} of "
                             f"{int(counts['xla'].sum())} expert "
                             f"assignments differ between the forms "
                             f"(limit {KEYE_FLIPS})")
    report = []
    for i, what in enumerate(("mixed round", "decode step", "gdn state")):
        ref, got = out["xla"][i], out["kernel"][i]
        scale = float(np.abs(ref).max())
        by_row = np.abs(got - ref).max(axis=1)
        over = by_row > LOGIT_RTOL * scale
        allowed = flips if what == "decode step" else 0
        if (not np.isfinite(got).all() or over.sum() > allowed
                or by_row.max() > scale / spec.top_k):
            raise AssertionError(
                f"qwen3_next {what}: with the kernels "
                f"{float(by_row.max()):.4g} from the XLA forms (largest "
                f"{scale:.4g}; by lane {np.round(by_row, 4).tolist()}; "
                f"{flips} expert assignments differ)")
        report.append(f"{what} err {float(by_row.max()):.4g} of {scale:.4g}")
    here = counts["xla"][:, first:first + held].sum()
    return ("; ".join(report) + f"; {flips} of {int(counts['xla'].sum())} "
            f"decode assignments differ, {int(here)} of them on experts "
            f"{first}..{first + held} held here")


def phase_evabyte(smoke: Smoke) -> str:
    """One stream through the scheduler with the kernels on: a prompt eight
    rows short of its third window (two compactions in mixed rounds), then
    sixteen greedy tokens across the third boundary (a compaction between
    decode blocks), its log-probabilities against the benchmark's plain
    float32 reference, which knows no page, row or kernel."""
    import importlib.util
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.engine.paged_steps import paged_eva_compact
    from tpulab.models.spec import evabyte_spec, init_params
    cfg = smoke.sizes.evabyte
    spec = evabyte_spec(cfg)
    vocab, window, chunk = cfg["vocab_size"], spec.eva_window, spec.eva_chunk
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        init_params(spec, vocab, cfg["intermediate_size"]))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "perf",
                        "reference", "evabyte.py")
    mod_spec = importlib.util.spec_from_file_location("ref_evabyte", path)
    reference = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(reference)
    steps = 16
    prompt = np.random.default_rng(6).integers(
        0, vocab, 3 * window - 8).tolist()
    cb = ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                           lanes=2, max_len=4 * window, page_size=chunk,
                           compute_dtype=jnp.bfloat16, use_kernel=True)
    try:
        free = cb.pool.free_pages
        check_mosaic(smoke, "evabyte compaction", partial(
            paged_eva_compact, spec=spec, use_kernel=True), params,
            cb.pool.kv, jnp.arange(1, 1 + window // chunk, dtype=jnp.int32))
        toks, lps = cb.submit(prompt, steps=steps, logprobs=True).result(
            timeout=900)
        eva = cb.debug_state()["eva"]
        home = cb.pool.free_pages == free
    finally:
        cb.shutdown()
    got = reference.compare(params, prompt, toks, lps,
                            **reference.hyper_of(cfg))
    if eva["compactions"] != {"round": 2, "decode": 1} or not home:
        raise AssertionError(f"evabyte: compactions {eva['compactions']}, "
                             f"pages home: {home}")
    if not max(got["logprob_err"], got["argmax_gap"]) <= reference.TOLERANCE \
            or not got["logprob_err_max"] <= reference.MAX_TOLERANCE:
        raise AssertionError(f"evabyte: the served stream is {got} from the "
                             f"reference (limit {reference.TOLERANCE})")
    return (f"prompt of {len(prompt)} and {steps} tokens across position "
            f"{3 * window}: logprob_err median {got['logprob_err']:.4g} "
            f"(limit {reference.TOLERANCE}) largest "
            f"{got['logprob_err_max']:.4g} (limit "
            f"{reference.MAX_TOLERANCE}); {eva['rows_compacted']} rows "
            f"compacted, {eva['pages_released']} pages returned")


# -- phase 4: the Pallas kernels, compiled by Mosaic -------------------------
def round_buffer(tables, toks, row_lane, row_off, q_lens, kv_lens):
    """A mixed round of greedy lanes as the ONE buffer ``paged_mixed_step``
    takes (what ``ContinuousBatcher._ragged_round`` sends)."""
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.paged_steps import (ROUND_STOPS, dispatch_fields,
                                           pack_words)
    lanes, max_pages = tables.shape
    i32 = lambda x: np.asarray(x, np.int32)  # noqa: E731
    return jnp.asarray(pack_words(
        dispatch_fields("round", lanes, max_pages), dict(
            tables=i32(tables), q_lens=i32(q_lens), kv_lens=i32(kv_lens),
            temps=np.zeros((lanes,), np.float32),
            seeds=np.zeros((lanes, 2), np.uint32),
            fresh=np.ones((lanes,), bool), rem=np.zeros((lanes,), np.int32),
            stops=np.full((lanes, ROUND_STOPS), -1, np.int32),
            rows=np.stack([i32(toks), i32(row_lane), i32(row_off)]))))


def no_carry(lanes: int):
    """The carry a round takes beside a buffer that is ``fresh`` in every
    lane: only its shapes count."""
    import jax.numpy as jnp
    return tuple(jnp.zeros((lanes,), t)
                 for t in (jnp.int32, jnp.int32, bool, jnp.int32))


def check_mosaic(smoke: Smoke, name: str, fn, *args) -> None:
    """On the chip the lowered program must hold the Mosaic custom call;
    in the rehearsal (interpret mode) it must not."""
    import jax
    has = "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()
    if has == smoke.rehearsal:
        raise AssertionError(
            f"{name}: Mosaic custom call {'present' if has else 'absent'} "
            f"in the lowered program")


def ragged_case(smoke: Smoke, name: str, q_lens, kv_lens, m: int,
                dtype=None, g_pages=None, nbuf=None, heads=None,
                tol: float = ATTN_TOL) -> float:
    """ragged_paged_attention vs the XLA gather path on one segment mix at
    the LM's head geometry, on the second layer of a two-layer page store
    (the kernel indexes the layer itself: a wrong offset reads the first).
    Returns the max abs error over valid rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.paged_steps import _gather_attend
    from tpulab.ops.ragged_attention import ragged_paged_attention
    sz = smoke.sizes
    dtype = dtype or jnp.bfloat16
    h, hkv = heads or (sz.lm["n_heads"], sz.lm["n_kv_heads"])
    d = sz.lm["d_model"] // sz.lm["n_heads"]
    ps = sz.lm_page_size
    q_lens = np.asarray(q_lens, np.int32)
    kv_lens = np.asarray(kv_lens, np.int32)
    b = len(q_lens)
    mp = -(-int(kv_lens.max()) // ps)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, m, h, d)), dtype)
    pool = jnp.asarray(
        rng.standard_normal((2, b * mp + 1, 2, ps, hkv * d)), dtype)
    tables = (1 + np.arange(b * mp, dtype=np.int32)).reshape(b, mp)
    interpret = smoke.rehearsal

    def kernel(q, pool):
        return ragged_paged_attention(q, pool, 1, tables, q_lens, kv_lens,
                                      interpret=interpret, g_pages=g_pages,
                                      nbuf=nbuf)

    check_mosaic(smoke, name, kernel, q, pool)
    got = np.asarray(jax.block_until_ready(kernel(q, pool)), np.float32)
    pos = (kv_lens - q_lens)[:, None] + np.arange(m)[None, :]
    want = np.asarray(_gather_attend(
        q, pool[1, :, 0], pool[1, :, 1], jnp.asarray(tables),
        jnp.asarray(pos),
        jnp.float32), np.float32).reshape(b, m, h, d)
    valid = np.arange(m)[None, :] < q_lens[:, None]
    if not np.isfinite(got[valid]).all():
        raise AssertionError(f"{name}: non-finite kernel output")
    err = float(np.abs(got - want)[valid].max())
    if err > tol:
        raise AssertionError(f"{name}: kernel vs XLA gather max abs error "
                             f"{err:.3g} > {tol}")
    return err


def phase_kernels(smoke: Smoke) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.paged import ContinuousBatcher
    sz = smoke.sizes
    ps, top = sz.lm_page_size, sz.lm_max_len
    # the widest segment the engine dispatches un-chunked
    chunk = min(ContinuousBatcher.RAGGED_CHUNK_CAP, top)
    errs = {}
    # decode (q_len = 1), K+1 verify (one lane inactive), and a mixed
    # round: a full prompt chunk beside decode lanes
    errs["decode"] = ragged_case(
        smoke, "ragged decode", [1, 1, 1, 1], [3, ps + 1, top, top // 2 + 5],
        m=1)
    errs["verify"] = ragged_case(
        smoke, "ragged verify", [5, 5, 3, 0], [9, top // 4 + 5, top, 0], m=5)
    errs["mixed"] = ragged_case(
        smoke, "ragged mixed", [chunk, 1, chunk // 2 + 5, 1],
        [chunk, top // 2, top // 2 + 9, top], m=chunk)
    # more blocks than pipeline slots, f32 so the tolerance is tight: an
    # async page DMA racing the slot about to be read shows only on
    # hardware (interpret-mode DMAs are synchronous)
    errs["refill_f32"] = ragged_case(
        smoke, "ragged slot refill", [1, 1], [16 * ps - 1, 8 * ps + 2], m=1,
        dtype=jnp.float32, g_pages=2, nbuf=3, heads=(2, 2), tol=2e-3)

    shown = " ".join(f"{k}={v:.2g}" for k, v in errs.items())
    return f"max_abs_err_vs_xla: {shown} (tol {ATTN_TOL}, f32 case 2e-3)"


# -- phase 5: more than one device -------------------------------------------
def round_logits(cb, prompt):
    """Last-position logits of ``prompt`` from the engine's own jitted
    mixed round (its shardings included): the whole prompt as lane 0's
    chunk, over a scratch page pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpulab.engine.paged_steps import ROUND_STOPS, pack_round, pack_words
    pool = cb.pool
    kv = jax.device_put(jnp.zeros(pool._shape, pool.dtype), pool.placement)
    b = cb.lanes
    toks, row_lane, row_off, q_lens = pack_round(b, {0: prompt}, {})
    tables = np.zeros((b, cb.max_pages), np.int32)
    n = -(-len(prompt) // cb.page_size)
    tables[0, :n] = 1 + np.arange(n)
    packed = pack_words(cb.programs.fields["round"], dict(
        tables=tables, q_lens=q_lens, kv_lens=q_lens,
        temps=np.zeros((b,), np.float32), seeds=np.zeros((b, 2), np.uint32),
        fresh=np.ones((b,), bool), rem=np.zeros((b,), np.int32),
        stops=np.full((b, ROUND_STOPS), -1, np.int32),
        rows=np.stack([toks, row_lane, row_off])))
    _out, last, *_ = cb.programs.mixed(cb.params, kv, cb._put(packed),
                                       cb._no_carry)
    return np.asarray(last[0], np.float32)


def phase_multichip(smoke: Smoke) -> str:
    import jax
    import numpy as np

    import tpulab
    from tpulab.parallel.dispatch import MultiDeviceDispatcher
    from tpulab.parallel.mesh import make_mesh

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return "multichip: not run (1 device)"
    sz = smoke.sizes

    # one RN50 replica per chip (one host weight set, a copy placed on
    # each): every chip serves and holds its own weights
    model = smoke.rn50(max_batch_size=8, batch_buckets=[1, 8])
    spec, out_spec = model.inputs[0], model.outputs[0]
    weight_bytes = model.weights_size_in_bytes()
    disp = MultiDeviceDispatcher.create(lambda: model, "rn50",
                                        devices=devices)
    try:
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, (8, *spec.shape)).astype(spec.np_dtype)
        futs = [disp.infer("rn50", **{spec.name: x}) for _ in range(2 * n)]
        outs = [f.result(timeout=300)[out_spec.name] for f in futs]
        if list(disp.served) != [2] * n:
            raise AssertionError(f"requests per device {disp.served}, "
                                 f"expected {[2] * n}")
        for o in outs[1:]:
            # same weights, same program, another chip: near bit-equal
            if not np.allclose(o, outs[0], rtol=1e-3, atol=1e-3):
                raise AssertionError("RN50 replicas disagree across devices")
        held = []
        for d in devices:
            stats = d.memory_stats() if not smoke.rehearsal else None
            held.append(stats["bytes_in_use"] if stats else None)
        if not smoke.rehearsal and min(held) < weight_bytes:
            raise AssertionError(
                f"a device holds {min(held)} bytes, less than one RN50 "
                f"weight copy ({weight_bytes}): {held}")
    finally:
        disp.shutdown()

    # the LM tensor-parallel over every chip, against the single-device run
    params = lm_params(smoke)
    mesh = make_mesh({"model": n}, devices)
    single = lm_engine(smoke, params, use_kernel=False)
    sharded = lm_engine(smoke, params, mesh=mesh, use_kernel=False)
    # and the ragged kernel under shard_map, each chip walking its own
    # KV heads' pages
    sharded_kernel = lm_engine(smoke, params, mesh=mesh, use_kernel=True)
    manager = tpulab.InferenceManager(max_exec_concurrency=1)
    remote = None
    try:
        prompt = np.random.default_rng(2).integers(
            0, sz.lm["vocab"], (sz.lm_prompt_lens[1],)).astype(np.int32)
        a, b = round_logits(single, prompt), round_logits(sharded, prompt)
        err = float(np.abs(a - b).max() / max(1.0, float(np.abs(a).max())))
        if not np.isfinite(b).all() or err > LOGIT_RTOL:
            raise AssertionError(
                f"{{'model': {n}}} round logits differ from single-device "
                f"by {err:.3g} (limit {LOGIT_RTOL})")
        manager.serve(port=0, generation_engines={
            "lm_mesh": sharded, "lm_mesh_kernel": sharded_kernel})
        remote = tpulab.RemoteInferenceManager(
            f"localhost:{manager.server.bound_port}")
        tokens = (stream_generations(smoke, remote, "lm_mesh")
                  + stream_generations(smoke, remote, "lm_mesh_kernel"))
    finally:
        if remote is not None:
            remote.close()
        manager.shutdown()
        for cb in (single, sharded, sharded_kernel):
            cb.shutdown()
    return (f"multichip: {n} devices; rn50 served={list(disp.served)} "
            f"bytes_in_use={held} (weights {weight_bytes}); lm mesh "
            f"{{'model': {n}}} logits rel err {err:.2g}, {tokens} tokens "
            "streamed (gather and kernel attention)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU backend; never a pass "
                         f"(exit {EXIT_REHEARSAL}, no result line)")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    if args.rehearse_cpu:
        # mesh code needs devices; must precede the first backend use
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    import jax

    dev = jax.devices()[0]
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: no accelerator: jax found platform="
              f"{dev.platform!r} ({dev.device_kind}), need {want!r}",
              file=sys.stderr)
        return EXIT_NO_CHIP
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    smoke = Smoke(args.rehearse_cpu)

    with tempfile.TemporaryDirectory(prefix="tpulab-native-") as tmp:
        lib = build_native_core(tmp)
        os.environ["TPULAB_NATIVE_LIB"] = lib
        from tpulab import native
        from tpulab.tpu.platform import pallas_interpret
        if native.loaded_path() != lib:
            raise AssertionError(f"native core loaded from "
                                 f"{native.loaded_path()!r}, built {lib!r}")
        if pallas_interpret() != args.rehearse_cpu:
            raise AssertionError(
                f"pallas_interpret() is {pallas_interpret()} on "
                f"{dev.platform}")
        smoke.stamp = (f"platform={device['platform']} "
                       f"device_kind={device['kind']!r} "
                       f"devices={device['count']} jax={jax.__version__} "
                       f"native_core={str(native.enabled()).lower()}")
        smoke.say(f"phase device: ok native core {native.version()} built "
                  f"from cpp/, pallas_interpret={pallas_interpret()}")
        smoke.run("rn50", phase_rn50)
        smoke.run("lm", phase_lm)
        smoke.run("latent", phase_latent)
        smoke.run("jamba", phase_jamba)
        smoke.run("keye_vl2", phase_keye)
        smoke.run("qwen3_next", phase_qwen3_next)
        smoke.run("evabyte", phase_evabyte)
        smoke.run("kernels", phase_kernels)
        smoke.run("multichip", phase_multichip)

    if args.rehearse_cpu:
        smoke.say("rehearsal finished; run without --rehearse-cpu on the "
                  "chip for a result")
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
