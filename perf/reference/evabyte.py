"""Plain reference: EvaByte (``model_type`` ``evabyte``, ``attention_class``
``eva``).

Written from the published ``config.json`` keys and the published description
of EVA attention (``modeling_evabyte.py`` / ``eva.py`` as remembered: there is
no network here, so what the keys do not settle is listed under ``assumed`` in
the configuration file); straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no batching,
no page table, no logical rows, nothing imported from the program.

``N(x) = x / rms(x) * (1 + w)`` at ``rms_norm_eps`` (``norm_add_unit_offset``;
the served weights hold ``scale = 1 + w``).  A layer::

    h <- x + Attn(N(x)) W_o
    y <- h + W_down(silu(W_gate N(h)) * W_up N(h))

*EVA attention*, a head ``h`` of width ``d``, window ``W``, chunk ``C``, chunk
``c`` = positions ``[C c, C c + C)``, ``w(i) = i // W``:

1. ``q_i, k_i, v_i`` from the three projections; rotate-half RoPE over all
   ``d`` columns at ``rope_theta`` at the TRUE position ``i``, on q and k;
2. for every complete chunk, from the roped keys: ``k~_c = sum_m a_m k_m``,
   ``a = softmax_{m in c}(mu_h . k_m)``; ``v~_c = sum_m b_m v_m``, ``b =
   softmax_{m in c}(phi_h . k_m)``;
3. query ``i`` sees ``S_i = {j : W w(i) <= j <= i}`` (its own window, exact)
   and ``R_i = {c : c < (W / C) w(i)}`` (the chunks of every EARLIER window),
   in ONE softmax at ``d^-0.5`` over the ``n`` raw scores and the ``n / C``
   summary scores, both masked by that definition: a full score matrix, in
   blocks of queries.

Final norm; the logits of prediction head 0 (``lm_head``; the further heads,
``mtp_heads``, are held and not run).

It is handed the *served* weights (bf16, the program's layout, documented in
``tpulab/models/spec.py``) and upcasts one layer at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream (after a prompt of 4,090 they
#: cross position 4,096, so a compaction happens in decode), and streams
#: asked at each prompt length (prompts drawn apart: a greedy stream on
#: seeded weights settles on a few ids).
REFERENCE_STEPS = 32
REFERENCE_STREAMS = 2

#: Largest MEDIAN, over the emitted tokens of the streams of one prompt
#: length, of the difference between the served path and this reference, in
#: logit units, on (a) the log-probability of each emitted token and (b) how
#: far the emitted token's reference logit lies under the reference's
#: largest.  The model is dense (no routing to flip), so every token carries
#: the same rounding and the median over 64 tokens is steady over seeds
#: where the largest of them swings by a factor of two.
#:
#: Its size, from TOLERANCE_READINGS (my chip runs, PR 41: on the v5e at the
#: published widths through the Generate RPC under the cell's engine; after
#: the 24-byte prompts / after the 4,090-byte prompts; ``bf16`` over the seeds
#: of every run made, the faults on one or two seeds): between bf16
#: serving's largest reading and the smallest fault's, which is the next
#: precision below what the configuration states (an fp8 K/V store).  The
#: faults that change WHAT a query sees (no summaries, a window not reset,
#: summaries of the query's own window, uniform pooling) read 0.19-2.8 on the
#: long prompts and exactly bf16's on the short ones, which end inside one
#: window: nothing but the long prompts can see them.
TOLERANCE = 0.06
#: The LARGEST ``logprob_err`` of those tokens: what a fault that touches few
#: tokens (the first rows past a boundary) would move and the median would
#: not.  bf16 serving's largest reading swings with the seed, so it has more
#: room above than the median's limit has.
MAX_TOLERANCE = 0.3
#: Largest MEDIAN, over the rows a stream's lane held when it ended (layer
#: 0: the summaries of its finished windows, then the rows of its last
#: window), of a row's difference from the reference's over the row's norm,
#: the larger of keys and values, the larger of the streams: what a store
#: kept in a lower precision than the configuration states moves, whatever
#: the logits show of it, and what a wrong pooling moves (0.89).
KV_TOLERANCE = 0.012
TOLERANCE_READINGS: Dict[str, Dict[str, str]] = {
    "logprob_err": {
        "bf16": "0.020-0.032 / 0.019-0.029 (ten seeds)",
        "fp8_kv": "0.158 / 0.144",
        "no_summaries": "0.027 / 2.8",
        "window_not_reset": "0.027 / 0.22",
        "window_half": "0.027 / 0.19",
        "uniform_mu": "0.027 / 1.7",
        "uniform_phi": "0.027 / 1.8",
    },
    "logprob_err_max": {
        "bf16": "0.080-0.112 / 0.079-0.100 (ten seeds)",
        "fp8_kv": "0.556 / 0.558",
    },
    "kv_err": {
        "bf16": "0.00278-0.00280 / 0.00468-0.00476 (ten seeds)",
        "fp8_kv": "0.029 / 0.044",
        "uniform_mu": "0.0028 / 0.89",
    },
}


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, positions, theta):
    """Rotate-half RoPE over the whole head: x (T, H, D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def chunk_summaries(k, v, mu, phi, chunk: int):
    """``(k~ (n, H, D), v~ (n, H, D))`` of the ``n = T // chunk`` complete
    chunks of roped keys ``k`` and values ``v`` ``(T, H, D)``: two softmax
    poolings over a chunk's positions, a head at a time, scored by ``mu`` and
    ``phi`` ``(H, D)`` against the KEYS."""
    n = k.shape[0] // chunk
    kc = k[:n * chunk].reshape((n, chunk) + k.shape[1:])
    vc = v[:n * chunk].reshape(kc.shape)
    a = jax.nn.softmax(jnp.einsum("cmhd,hd->cmh", kc, mu), axis=1)
    b = jax.nn.softmax(jnp.einsum("cmhd,hd->cmh", kc, phi), axis=1)
    return (jnp.einsum("cmh,cmhd->chd", a, kc),
            jnp.einsum("cmh,cmhd->chd", b, vc))


@partial(jax.jit, static_argnames=("eps", "theta", "n_heads", "window",
                                   "chunk", "block"))
def _layer(x, ln1, wqkv, wo, mu, phi, ln2, w1, w2, w3, *, eps, theta,
           n_heads, window, chunk, block):
    """One decoder layer over the whole sequence x (T, d) in float32:
    ``(x, k (T, H, D) roped, v, k~ (T // C, H, D), v~)``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t, d = x.shape
        hd = d // n_heads
        h = _rmsnorm(x, ln1, eps)
        q, k, v = ((h @ wqkv.astype(f32)[:, i * d:(i + 1) * d]).reshape(
            t, n_heads, hd) for i in range(3))
        pos = jnp.arange(t)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        ks, vs = chunk_summaries(k, v, mu.astype(f32), phi.astype(f32), chunk)
        per = window // chunk                    # summaries a window
        cidx = jnp.arange(ks.shape[0])
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            i = pos[s:e, None]
            raw = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e]) / np.sqrt(hd)
            seen = (pos[None, :e] <= i) & (pos[None, :e] >= window * (
                i // window))
            raw = jnp.where(seen[None], raw, -jnp.inf)
            summ = jnp.einsum("qhd,chd->hqc", q[s:e], ks) / np.sqrt(hd)
            summ = jnp.where((cidx[None, :] < per * (i // window))[None],
                             summ, -jnp.inf)
            probs = jax.nn.softmax(jnp.concatenate([raw, summ], -1), axis=-1)
            outs.append((jnp.einsum("hqk,khd->qhd", probs[..., :e], v[:e])
                         + jnp.einsum("hqc,chd->qhd", probs[..., e:], vs)
                         ).reshape(e - s, d))
        x = x + jnp.concatenate(outs, 0) @ wo.astype(f32)
        h = _rmsnorm(x, ln2, eps)
        ff = jax.nn.silu(h @ w1.astype(f32)) * (h @ w3.astype(f32))
        return x + ff @ w2.astype(f32), k, v, ks, vs


@partial(jax.jit, static_argnames=("eps",))
def _head(x_last, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale, eps) @ lm_head.astype(jnp.float32)


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys."""
    return dict(n_layers=int(config["num_hidden_layers"]),
                rms_norm_eps=float(config["rms_norm_eps"]),
                rope_theta=float(config["rope_theta"]),
                n_heads=int(config["num_attention_heads"]),
                window=int(config["window_size"]),
                chunk=int(config["chunk_size"]))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, rms_norm_eps: float, rope_theta: float,
                n_heads: int, window: int, chunk: int, block: int = 256,
                stores: bool = False):
    """Float32 logits (n_last, vocab) of prediction head 0 at the last
    ``n_last`` positions of one full forward pass over ``tokens``; with
    ``stores`` also what a server would hold of LAYER 0 after it: ``(logits,
    kv (2, rows, H * D))``, the summaries of every finished window but the
    last position's own, then that window's key and value rows."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    kv = None
    for i in range(n_layers):
        p = params[f"layer{i}"]
        x, k, v, ks, vs = _layer(
            x, p["ln1"]["scale"], p["wqkv"], p["wo"], p["eva_mu"],
            p["eva_phi"], p["ln2"]["scale"], p["w1"], p["w2"], p["w3"],
            eps=rms_norm_eps, theta=float(rope_theta), n_heads=n_heads,
            window=window, chunk=chunk, block=block)
        if stores and i == 0:
            t = len(tokens)
            done = (t - 1) // window          # windows before the last row's
            kv = np.asarray(jnp.stack([
                jnp.concatenate([s[:done * (window // chunk)],
                                 r[done * window:]]).reshape(-1, x.shape[-1])
                for s, r in ((ks, k), (vs, v))]))
    logits = np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                              params["lm_head"], eps=rms_norm_eps),
                        np.float32)
    return (logits, kv) if stores else logits


def kv_error(served: np.ndarray, want: np.ndarray) -> float:
    """Layer 0's rows as the server held them against the reference's
    (``(2, rows, H * D)`` each): the larger, of keys and values, of the
    MEDIAN over the rows of a row's difference over the row's norm; infinite
    where the server holds another number of rows than the reference."""
    if served.shape != want.shape:
        return float("inf")     # another layout than the reference's: no match
    want = want.astype(np.float64)
    off = (np.linalg.norm(served - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    return float(np.median(off, axis=-1).max())


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 stores=None, **hyper) -> Dict[str, Any]:
    """One served greedy stream against the reference: one forward over
    ``prompt + tokens[:-1]``, whose last ``len(tokens)`` logit rows predict
    ``tokens``.  Per token: ``logprob_err``, the served log-probability
    against the reference's, and ``argmax_gap``, the reference's largest
    logit minus its logit of the emitted token.  With ``stores`` (layer 0's
    rows the server held once the stream had ended, every token of that
    forward taken in and nothing else) also ``kv_err`` (:func:`kv_error`),
    from the same forward."""
    n = len(tokens)
    fed = list(prompt) + list(tokens[:-1])
    logits = last_logits(params, fed, n, stores=stores is not None, **hyper)
    out: Dict[str, Any] = {}
    if stores is not None:
        logits, want = logits
        out["kv_err"] = kv_error(stores, want)
    logits = logits.astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows = np.arange(n)
    toks = np.asarray(tokens)
    return dict(out, logprob_err=np.abs(logp[rows, toks]
                                        - np.asarray(logprobs)),
                argmax_gap=logits.max(-1) - logits[rows, toks])


def summary(streams: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """The tokens of ``streams`` (:func:`token_errors` of each) judged
    together: the MEDIANS of ``logprob_err`` and ``argmax_gap`` over all the
    tokens (what TOLERANCE judges), the largest ``logprob_err``
    (``logprob_err_max``: MAX_TOLERANCE) and, where the streams carry it,
    the largest ``kv_err`` (KV_TOLERANCE)."""
    err = np.concatenate([s["logprob_err"] for s in streams])
    gap = np.concatenate([s["argmax_gap"] for s in streams])
    out = {"logprob_err": float(np.median(err)),
           "argmax_gap": float(np.median(gap)),
           "logprob_err_max": float(err.max())}
    if all("kv_err" in s for s in streams):
        out["kv_err"] = float(max(s["kv_err"] for s in streams))
    return out


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """:func:`summary` of one stream alone."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
