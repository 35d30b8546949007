"""Plain reference: a pre-norm decoder-only transformer (the Mistral-7B block).

Written from the published description (Mistral 7B, arXiv:2310.06825, and the
model's ``config.json``): RMSNorm -> grouped-query attention with rotary
position embedding (rotate-half convention, ``rope_theta`` from the config)
-> residual; RMSNorm -> SwiGLU feed-forward ``down(silu(gate(x)) * up(x))``
-> residual; final RMSNorm; untied output head.  Straightforward
``jax.numpy`` in float32 with ``jax.default_matmul_precision("highest")``:
no kernel, no cache, no batching, nothing imported from the program.

It is handed the *served* weights (bf16, the program's layout: ``wqkv`` is
``[q | k | v]`` column-wise, ``w1`` the gate, ``w3`` the up and ``w2`` the
down projection) and upcasts one layer at a time inside that layer's jitted
function, so sixteen layers never sit in float32 beside the served model.
Attention runs in blocks of query positions against the whole context.

Departure, shared with the program and stated in the configuration file:
the RMSNorm epsilon is ``EPS`` below, not the config's ``rms_norm_eps``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: the program's constant (``tpulab/models/transformer.py`` ``_rmsnorm``)
EPS = 1e-6

#: Largest difference allowed between the served path and this reference,
#: in logit units (natural log), on (a) the log-probability of every emitted
#: token and (b) how far the emitted token's reference logit may lie under
#: the reference's largest.  Why this size: the served path keeps
#: activations in bf16 (rounding 2**-9 relative) through 16 layers with
#: float32 accumulation; on the v5e, at the published widths, that read
#: 0.04-0.17 (median 0.10) against this reference on logits of standard
#: deviation ~1.3, over the hundred checks of PR 24's runs, so 0.2 would
#: fail a sound run now and then.  What it catches (one scratch run each,
#: PR 24, PERF.md section 6): the KV cache in fp8 (e4m3) read 0.40 and 0.49
#: and fails on both prompts; weight-only int8 read 0.33 on the 24-token
#: prompt and fails there, but 0.17 on the long one, inside bf16's own
#: band: a maximum over 8 tokens cannot tell it apart on every prompt.
TOLERANCE = 0.25


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x (T, H, D); rotate-half convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "theta", "block"))
def _layer(x, ln1, wqkv, wo, ln2, w1, w2, w3, *, n_heads, n_kv_heads, theta,
           block):
    """One decoder layer over the whole sequence x (T, d) in float32."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t, d = x.shape
        hd = d // n_heads
        g = n_heads // n_kv_heads
        h = _rmsnorm(x, ln1)
        qkv = h @ wqkv.astype(f32)
        q = qkv[:, :n_heads * hd].reshape(t, n_heads, hd)
        k = qkv[:, n_heads * hd:(n_heads + n_kv_heads) * hd].reshape(
            t, n_kv_heads, hd)
        v = qkv[:, (n_heads + n_kv_heads) * hd:].reshape(t, n_kv_heads, hd)
        pos = jnp.arange(t)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        # query head i reads key/value head i // g
        qg = q.reshape(t, n_kv_heads, g, hd)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = jnp.einsum("qhgd,khd->hgqk", qg[s:e], k[:e]) / np.sqrt(hd)
            mask = pos[s:e, None] >= pos[None, :e]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            outs.append(jnp.einsum("hgqk,khd->qhgd", probs, v[:e])
                        .reshape(e - s, d))
        x = x + jnp.concatenate(outs, 0) @ wo.astype(f32)
        h = _rmsnorm(x, ln2)
        ff = (jax.nn.silu(h @ w1.astype(f32)) * (h @ w3.astype(f32)))
        return x + ff @ w2.astype(f32)


@jax.jit
def _head(x_last, scale, lm_head):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale) @ lm_head.astype(jnp.float32)


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, n_heads: int, n_kv_heads: int,
                rope_theta: float, block: int = 256) -> np.ndarray:
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    for i in range(n_layers):
        p = params[f"layer{i}"]
        x = _layer(x, p["ln1"]["scale"], p["wqkv"], p["wo"],
                   p["ln2"]["scale"], p["w1"], p["w2"], p["w3"],
                   n_heads=n_heads, n_kv_heads=n_kv_heads,
                   theta=float(rope_theta), block=block)
    return np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                            params["lm_head"]), np.float32)


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """Hold a served greedy stream to the reference.  The reference runs
    one forward over ``prompt + tokens[:-1]``; row ``i`` of its last
    ``len(tokens)`` logit rows predicts ``tokens[i]``.  Returns the largest
    ``logprob_err`` (served log-probability against the reference's) and
    ``argmax_gap`` (reference's largest logit minus its logit of the token
    that was emitted: 0 where the two agree on the argmax)."""
    n = len(tokens)
    logits = last_logits(params, list(prompt) + list(tokens[:-1]), n, **hyper)
    logits = logits.astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows = np.arange(n)
    toks = np.asarray(tokens)
    return {
        "logprob_err": float(np.abs(logp[rows, toks]
                                    - np.asarray(logprobs)).max()),
        "argmax_gap": float((logits.max(-1) - logits[rows, toks]).max()),
    }
