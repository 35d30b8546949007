"""Decoder-only transformer — the long-context model family.

Not present in the reference (trtlab predates LLM serving — SURVEY §2.8 scope
note); included because the TPU build treats long-context/sequence scaling as
first-class.  The attention op is pluggable so the parallel layer can swap in
ring attention (:mod:`tpulab.parallel.ring_attention`) for sequence lengths
that exceed one chip's HBM.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def init_transformer_params(vocab: int = 32000, d_model: int = 512,
                            n_heads: int = 8, n_layers: int = 6,
                            d_ff: int = 2048, seed: int = 0,
                            n_kv_heads: Optional[int] = None,
                            ffn: str = "gelu",
                            tie_embeddings: bool = True) -> Dict[str, Any]:
    """``n_kv_heads < n_heads`` selects grouped-query attention (GQA;
    ``n_kv_heads=1`` is MQA): K/V projections shrink to ``n_kv_heads``
    heads, cutting KV-cache HBM and decode bandwidth by the group factor.
    Default (None) is standard multi-head attention.  ``ffn="swiglu"``
    adds the w3 gate projection (Llama family); ``tie_embeddings=False``
    adds an untied ``lm_head``."""
    n_kv = n_kv_heads or n_heads
    if n_heads % n_kv:
        raise ValueError(f"n_heads {n_heads} not divisible by "
                         f"n_kv_heads {n_kv}")
    head_dim = d_model // n_heads
    rng = jax.random.PRNGKey(seed)
    keys = iter(jax.random.split(rng, 4 * n_layers + 4))
    s = 0.02
    params: Dict[str, Any] = {
        "embed": jax.random.normal(next(keys), (vocab, d_model)) * s,
        "final_norm": {"scale": jnp.ones((d_model,))},
    }
    for i in range(n_layers):
        lkeys = iter(jax.random.split(next(keys), 8))
        params[f"layer{i}"] = {
            "ln1": {"scale": jnp.ones((d_model,))},
            "ln2": {"scale": jnp.ones((d_model,))},
            "wqkv": jax.random.normal(
                next(lkeys),
                (d_model, (n_heads + 2 * n_kv) * head_dim)) * s,
            "wo": jax.random.normal(next(lkeys), (d_model, d_model)) * s,
            "w1": jax.random.normal(next(lkeys), (d_model, d_ff)) * s,
            "w2": jax.random.normal(next(lkeys), (d_ff, d_model)) * s,
        }
        if ffn == "swiglu":
            params[f"layer{i}"]["w3"] = jax.random.normal(
                next(lkeys), (d_model, d_ff)) * s
        elif ffn != "gelu":
            raise ValueError(f"unknown ffn {ffn!r}")
    if not tie_embeddings:
        params["lm_head"] = jax.random.normal(next(keys),
                                              (d_model, vocab)) * s
    return params


def split_qkv(qkv, b, t, n_heads, n_kv_heads, head_dim):
    """Split a fused QKV projection into (q (B,T,Hq,D), k/v (B,T,Hkv,D))."""
    q_dim = n_heads * head_dim
    kv_dim = n_kv_heads * head_dim
    q = qkv[..., :q_dim].reshape(b, t, n_heads, head_dim)
    k = qkv[..., q_dim:q_dim + kv_dim].reshape(b, t, n_kv_heads, head_dim)
    v = qkv[..., q_dim + kv_dim:].reshape(b, t, n_kv_heads, head_dim)
    return q, k, v


def repeat_kv(kv, n_heads):
    """Broadcast (…, Hkv, D) K/V heads up to the query head count (GQA)."""
    hkv = kv.shape[-2]
    if hkv == n_heads:
        return kv
    return jnp.repeat(kv, n_heads // hkv, axis=-2)


def apply_rope(x, positions, theta: float = 10000.0, inv_freq=None):
    """Rotary position embedding (HF Llama rotate-half convention).

    x (..., T, H, D); positions (..., T) int — broadcast against x's batch
    dims.  K is rotated BEFORE cache/pool writes, so cached keys are
    position-baked and attention needs no further rotation.  ``inv_freq``
    ``(D / 2,)`` float32, where a model scales its frequencies
    (:meth:`tpulab.models.spec.ModelSpec.rope_inv_freq`), stands in for
    ``theta``'s.
    """
    d = x.shape[-1]
    half = d // 2
    inv = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
           if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    ang = positions.astype(jnp.float32)[..., None] * inv   # (..., T, half)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]  # (.., T, 1, D)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rot * sin).astype(x.dtype)


def qmat(w, compute_dtype):
    """Weight matrix ready for matmul, transparently dequantizing
    weight-only INT8 entries ({"w_int8", "scale"} from
    :func:`tpulab.models.quantization.quantize_transformer_params`).

    TPU-first W8A16: the int8 matrix is what lives in (and streams from)
    HBM — the 2-4x smaller read is the win, since small-batch decode is
    weight-bandwidth-bound; the cast and per-output-channel scale are
    cheap VPU work XLA fuses into the consuming matmul's operand read.
    """
    if isinstance(w, dict) and "w_int8" in w:
        return (w["w_int8"].astype(compute_dtype)
                * w["scale"].astype(compute_dtype))
    return w.astype(compute_dtype)


def weight_shape(w):
    """Shape of a (possibly weight-only-quantized) weight matrix."""
    return (w["w_int8"] if isinstance(w, dict) and "w_int8" in w
            else w).shape


def _rmsnorm(x, scale, eps: float = 1e-6):
    """``eps`` is a model parameter (``ModelSpec.rms_eps``); the default is
    the constant the dense decoder has always been served with."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def dense_attention(q, k, v, causal: bool = True):
    """Single-device attention (B, T, H, D), optionally causal."""
    b, t, h, d = q.shape
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def causal_attention(q, k, v):
    """Default single-device causal attention (B, T, H, D)."""
    return dense_attention(q, k, v, causal=True)


def _dense_ffn(p, h, compute_dtype):
    """Default FFN block: SwiGLU when the layer has a ``w3`` gate
    projection (the Llama family), else w1/gelu/w2."""
    if "w3" in p:
        return (jax.nn.silu(h @ qmat(p["w1"], compute_dtype))
                * (h @ qmat(p["w3"], compute_dtype))) \
            @ qmat(p["w2"], compute_dtype)
    return jax.nn.gelu(h @ qmat(p["w1"], compute_dtype)) \
        @ qmat(p["w2"], compute_dtype)


def _lm_head(params, x):
    """Final projection: untied ``lm_head`` when present, else tied to the
    embedding matrix."""
    if "lm_head" in params:
        return x.astype(jnp.float32) @ qmat(params["lm_head"], jnp.float32)
    return x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32)


def _forward(params, tokens, n_heads, n_layers, compute_dtype, attention_fn,
             ffn_fn=_dense_ffn, n_kv_heads: Optional[int] = None,
             rope_theta: Optional[float] = None):
    """Shared transformer trunk: (B, T) tokens -> logits.
    ``ffn_fn(layer_params, h, compute_dtype)`` swaps the FFN (dense / MoE).
    ``rope_theta`` enables rotary embeddings at absolute positions 0..T-1.
    Under sequence parallelism pass pre-roped inputs or keep rope off
    here."""
    n_kv = n_kv_heads or n_heads
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens]
    b, t, d_model = x.shape
    head_dim = d_model // n_heads
    positions = jnp.arange(t) if rope_theta else None
    for i in range(n_layers):
        p = params[f"layer{i}"]
        h = _rmsnorm(x, p["ln1"]["scale"])
        qkv = h @ qmat(p["wqkv"], compute_dtype)
        q, k, v = split_qkv(qkv, b, t, n_heads, n_kv, head_dim)
        if rope_theta:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
        attn = attention_fn(q, repeat_kv(k, n_heads),
                            repeat_kv(v, n_heads)).reshape(b, t, d_model)
        x = x + attn @ qmat(p["wo"], compute_dtype)
        h = _rmsnorm(x, p["ln2"]["scale"])
        x = x + ffn_fn(p, h, compute_dtype).astype(x.dtype)
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return _lm_head(params, x)


def transformer_apply(params: Dict[str, Any], inputs: Dict[str, jnp.ndarray],
                      n_heads: int = 8, n_layers: int = 6,
                      compute_dtype=jnp.bfloat16,
                      attention_fn: Callable = causal_attention,
                      n_kv_heads: Optional[int] = None,
                      rope_theta: Optional[float] = None
                      ) -> Dict[str, jnp.ndarray]:
    """tokens (B, T) int32 -> logits (B, T, vocab) f32."""
    return {"logits": _forward(params, inputs["tokens"], n_heads, n_layers,
                               compute_dtype, attention_fn,
                               n_kv_heads=n_kv_heads, rope_theta=rope_theta)}


def make_transformer(vocab: int = 32000, d_model: int = 512, n_heads: int = 8,
                     n_layers: int = 6, d_ff: int = 2048, seq_len: int = 1024,
                     max_batch_size: int = 4, compute_dtype=jnp.bfloat16,
                     seed: int = 0, attention_fn: Callable = causal_attention,
                     n_kv_heads: Optional[int] = None):
    from tpulab.engine.model import IOSpec, Model

    params = init_transformer_params(vocab, d_model, n_heads, n_layers,
                                     d_ff, seed, n_kv_heads=n_kv_heads)
    apply_fn = partial(transformer_apply, n_heads=n_heads, n_layers=n_layers,
                       compute_dtype=compute_dtype, attention_fn=attention_fn,
                       n_kv_heads=n_kv_heads)
    return Model(
        name="transformer",
        apply_fn=apply_fn,
        params=params,
        inputs=[IOSpec("tokens", (seq_len,), np.int32)],
        outputs=[IOSpec("logits", (seq_len, vocab), np.float32)],
        max_batch_size=max_batch_size,
    )


# ---------------------------------------------------------------------------
# KV-cache decode (autoregressive serving)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_layers: int, n_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Preallocated per-layer K/V rings (B, T_max, H, Dh) — pass the KV
    head count here (``n_kv_heads`` under GQA)."""
    shape = (batch, max_len, n_heads, head_dim)
    return {f"layer{i}": {"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)}
            for i in range(n_layers)}


def transformer_decode_step(params: Dict[str, Any], cache: Dict[str, Any],
                            tokens: jnp.ndarray, pos: jnp.ndarray,
                            n_heads: int = 8, n_layers: int = 6,
                            compute_dtype=jnp.bfloat16,
                            n_kv_heads: Optional[int] = None,
                            rope_theta: Optional[float] = None):
    """One decode step: tokens (B,) int32 at position ``pos`` (scalar int32).

    Returns (logits (B, vocab) f32, updated cache).  Attention runs against
    cache[: pos+1] via position masking — static shapes, scan/jit friendly
    (no data-dependent Python control flow).  Exactly the M=1 case of
    :func:`transformer_chunk_step` (single source of truth for the
    cache-attention math).
    """
    logits, new_cache = transformer_chunk_step(
        params, cache, tokens[:, None], jnp.asarray(pos),
        n_heads=n_heads, n_layers=n_layers, compute_dtype=compute_dtype,
        n_kv_heads=n_kv_heads, rope_theta=rope_theta)
    return logits[:, 0], new_cache


def transformer_chunk_step(params: Dict[str, Any], cache: Dict[str, Any],
                           tokens: jnp.ndarray, pos0: jnp.ndarray,
                           n_heads: int = 8, n_layers: int = 6,
                           compute_dtype=jnp.bfloat16,
                           n_kv_heads: Optional[int] = None,
                           rope_theta: Optional[float] = None):
    """Multi-token decode: process M new tokens (B, M) starting at position
    ``pos0`` (scalar int32) against the KV cache in ONE forward.

    Attention per chunk token m: all cache positions < pos0 + causal within
    the chunk.  Returns (logits (B, M, vocab) f32, updated cache).  This is
    the chunked-prefill AND speculative-verify primitive: a chunk of draft
    proposals verifies in one pass, and cache entries written past an
    eventual acceptance point are harmless — positions only advance, so
    stale slots are overwritten before they can ever be attended to.
    """
    n_kv = n_kv_heads or n_heads
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens]                                  # (B, M, D)
    b, m, d_model = x.shape
    head_dim = d_model // n_heads
    max_len = next(iter(cache.values()))["k"].shape[1]
    positions = pos0 + jnp.arange(m) if rope_theta else None
    new_cache = {}
    for i in range(n_layers):
        p = params[f"layer{i}"]
        h = _rmsnorm(x, p["ln1"]["scale"])
        qkv = h @ qmat(p["wqkv"], compute_dtype)
        q, k, v = split_qkv(qkv, b, m, n_heads, n_kv, head_dim)
        if rope_theta:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
        ck = jax.lax.dynamic_update_slice(
            cache[f"layer{i}"]["k"], k.astype(cache[f"layer{i}"]["k"].dtype),
            (0, pos0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache[f"layer{i}"]["v"], v.astype(cache[f"layer{i}"]["v"].dtype),
            (0, pos0, 0, 0))
        new_cache[f"layer{i}"] = {"k": ck, "v": cv}
        # mask: chunk token m attends cache position j iff j <= pos0 + m
        g = n_heads // n_kv
        qg = q.reshape(b, m, n_kv, g, head_dim)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            ck.astype(jnp.float32)) / np.sqrt(head_dim)
        k_pos = jnp.arange(max_len)
        vis = k_pos[None, :] <= (pos0 + jnp.arange(m))[:, None]   # (M, T)
        scores = jnp.where(vis[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
        attn = jnp.einsum("bhgqk,bkhd->bqhgd", probs,
                          cv.astype(compute_dtype)).reshape(b, m, d_model)
        x = x + attn @ qmat(p["wo"], compute_dtype)
        h2 = _rmsnorm(x, p["ln2"]["scale"])
        x = x + _dense_ffn(p, h2, compute_dtype).astype(x.dtype)
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return _lm_head(params, x), new_cache


def make_generate_fn(params: Dict[str, Any], n_heads: int, n_layers: int,
                     max_len: int, compute_dtype=jnp.bfloat16,
                     n_kv_heads: Optional[int] = None,
                     rope_theta: Optional[float] = None):
    """Jitted greedy generation: (prompt (B, T_p), steps) -> (B, steps).

    Prefill replays the prompt through scanned decode steps to warm the
    cache (a fused batched-prefill that writes the cache directly is the
    next optimization); decode is a lax.scan of cached steps —
    compiler-friendly: no growing shapes, no recompiles per step.
    """

    n_kv = n_kv_heads or n_heads

    def generate(prompt: jnp.ndarray, steps: int):
        b, t_p = prompt.shape
        head_dim = params["embed"].shape[1] // n_heads
        cache = init_kv_cache(b, max_len, n_layers, n_kv, head_dim,
                              compute_dtype)
        # prefill: run the full forward for logits, then replay the prompt
        # through decode steps to warm the cache (simple, correct; a fused
        # prefill that writes the cache directly is the next optimization)
        def prefill_body(carry, i):
            cache, _ = carry
            logits, cache = transformer_decode_step(
                params, cache, prompt[:, i], i, n_heads, n_layers,
                compute_dtype, n_kv_heads=n_kv, rope_theta=rope_theta)
            return (cache, logits), None

        (cache, logits), _ = jax.lax.scan(
            prefill_body, (cache, jnp.zeros((b, params["embed"].shape[0]))),
            jnp.arange(t_p))

        def decode_body(carry, i):
            cache, tok = carry
            logits, cache = transformer_decode_step(
                params, cache, tok, t_p + i, n_heads, n_layers,
                compute_dtype, n_kv_heads=n_kv, rope_theta=rope_theta)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt

        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        (_, _), toks = jax.lax.scan(decode_body, (cache, first),
                                    jnp.arange(steps - 1))
        return jnp.concatenate([first[:, None], toks.T], axis=1)

    return jax.jit(generate, static_argnums=1)


def early_exit_draft(target_params: Dict[str, Any],
                     draft_layers: int) -> Dict[str, Any]:
    """Self-speculative draft: the target's first ``draft_layers`` layers
    + its embed/final-norm/lm-head — 'early-exit' drafting (LayerSkip /
    Draft-&-Verify family).  No second model to train or ship: the draft
    IS a prefix of the target, so acceptance measures real early-exit
    agreement rather than a synthetic twin.

    The returned tree SHARES the target's weight arrays (no copy, no
    extra HBM beyond what the target already holds) and, by
    construction, the target's head geometry (head_dim, n_kv_heads) —
    exactly what the paged speculative path requires, since the draft's
    KV rides the target's :class:`~tpulab.engine.kv_pool.PagedKVPool`
    through a second page table (``ContinuousBatcher(draft_params=...,
    draft_n_layers=...)``).  The dense
    :class:`~tpulab.engine.speculative.SpeculativeGenerator` takes the
    same tree."""
    p = {"embed": target_params["embed"],
         "final_norm": target_params["final_norm"]}
    if "lm_head" in target_params:
        p["lm_head"] = target_params["lm_head"]
    for i in range(draft_layers):
        p[f"layer{i}"] = target_params[f"layer{i}"]
    return p


def make_moe_transformer(vocab: int = 32000, d_model: int = 512,
                         n_heads: int = 8, n_layers: int = 6,
                         d_ff: int = 2048, n_experts: int = 8,
                         top_k: int = 2, seq_len: int = 1024,
                         max_batch_size: int = 4,
                         compute_dtype=jnp.bfloat16, seed: int = 0,
                         attention_fn: Callable = causal_attention):
    """Transformer with MoE FFN blocks (per-layer expert banks; dense
    compute here, expert-parallel execution via
    tpulab.parallel.moe.make_expert_parallel_ffn over the same params)."""
    from tpulab.engine.model import IOSpec, Model
    from tpulab.parallel.moe import init_moe_params, moe_ffn

    rng = jax.random.PRNGKey(seed)
    keys = iter(jax.random.split(rng, 2 * n_layers + 2))
    s = 0.02
    params: Dict[str, Any] = {
        "embed": jax.random.normal(next(keys), (vocab, d_model)) * s,
        "final_norm": {"scale": jnp.ones((d_model,))},
    }
    for i in range(n_layers):
        params[f"layer{i}"] = {
            "ln1": {"scale": jnp.ones((d_model,))},
            "ln2": {"scale": jnp.ones((d_model,))},
            "wqkv": jax.random.normal(next(keys), (d_model, 3 * d_model)) * s,
            "wo": jax.random.normal(next(keys), (d_model, d_model)) * s,
            "moe": init_moe_params(d_model, d_ff, n_experts,
                                   seed=seed + i + 1),
        }

    def moe_block(lp, h, cdtype):
        b, t, dm = h.shape
        return moe_ffn(lp["moe"], h.reshape(b * t, dm), top_k=top_k,
                       compute_dtype=cdtype).reshape(b, t, dm)

    def apply_fn(p, inputs):
        return {"logits": _forward(p, inputs["tokens"], n_heads, n_layers,
                                   compute_dtype, attention_fn,
                                   ffn_fn=moe_block)}

    return Model(
        name="moe_transformer",
        apply_fn=apply_fn,
        params=params,
        inputs=[IOSpec("tokens", (seq_len,), np.int32)],
        outputs=[IOSpec("logits", (seq_len, vocab), np.float32)],
        max_batch_size=max_batch_size,
    )
