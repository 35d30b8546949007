"""Plain reference: the decoder of Keye-VL-2.0-30B-A3B (``model_type``
``KeyeVL2``).

Written from the published ``config.json`` (a Qwen3-MoE-shaped decoder with
an ``sa_config`` that names the DeepSeek-Sparse-Attention indexer) and the
conventions of the two families it names; straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: no kernel, no
cache, no batching, no grouped product, nothing imported from the program.

``N(.)`` is RMSNorm at ``rms_norm_eps``; RoPE is rotate-half at
``rope_theta`` (a text token has one position on all three M-RoPE axes, so
M-RoPE is RoPE).  A layer, hidden ``x`` (T, d), ``h = N(x)``:

*Attention* (``H`` query heads on ``Hkv`` KV heads of ``head_dim``, head
``j`` on KV head ``j // (H / Hkv)``): ``q = RoPE(N_head(h W_q))``, ``k =
RoPE(N_head(h W_k))``, ``v = h W_v`` (norms over the ``head_dim`` of each
head).  *Indexer* (``Hi`` heads of ``Di``, ONE index key a token): ``a =
RoPE(h W_iq)`` per head, ``b = RoPE(LayerNorm(h W_ik))``, ``c = h W_iw``;
``I_ts = (Hi * Di)^-0.5 * sum_i c_ti * relu(a_ti . b_s)`` for ``s <= t``;
``S_t`` = the ``min(t + 1, topk)`` keys of largest ``I_ts``, ties to the
lower ``s``: one set a token, shared by all heads.  ``o_tj = softmax over
S_t of (q_tj . k_s / sqrt(head_dim)) v_s``; ``x += concat_j(o) W_o``.
``I`` is computed here in tiles of ``q_chunk`` x ``kv_chunk`` (the
published ``q_chunk_size`` / ``kv_chunk_size``); the result does not depend
on them, and ``perf/tests`` hold it to that.

*MoE* (every layer; no shared expert, no bias): ``p = softmax(N(x) W_r)``
over all experts in float32; the ``num_experts_per_tok`` largest are chosen
(ties to the lower id); with ``norm_topk_prob`` their weights are ``p``
divided by their sum.  ``x += sum_e w_e W2_e(silu(W1_e h) * W3_e h)``: a
**loop over the experts**, each upcast alone and applied to the rows that
chose it.

Final norm, untied head.

It is handed the *served* weights (bf16, the program's layout, documented in
``tpulab/models/spec.py``): ``wqkv`` = ``[q | k | v]``; ``indexer`` ``wq``,
``wk``, ``k_norm {scale, bias}``, ``ww``; an expert's ``w13[e]`` = ``[gate |
up]``.  What the published keys do not settle is listed under ``assumed`` in
the configuration file, shared with the program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream (as kind ``glm4_moe_lite``:
#: enough for a quartile to mean something).
REFERENCE_STEPS = 32

#: Independent streams (prompts drawn apart) asked at each prompt length;
#: their tokens are judged TOGETHER, one quartile over all of them.  Why
#: more than one: with seeded weights a greedy stream settles on one or two
#: token ids within a dozen steps, so its tokens share one hidden state
#: and ONE error (a flipped expert, a rounding that repeats), not 32
#: draws.  Read a stream at a time, bf16 serving spans 0.0011-0.0300 after
#: the 24-token prompt over 96 streams and an fp8 K/V store 0.0040-0.0221
#: over 30: no limit lies between (the seed 1494666681 read 0.0141 on its
#: first short stream, every token from the 12th on the id 55155 with an
#: error of -0.014 to -0.025, and 0.0011-0.0051 on its next five).  Four
#: streams together: 0.0021-0.0044 against 0.0098-0.0119.
REFERENCE_STREAMS = 4

#: Largest LOWER QUARTILE, over the emitted tokens of the streams of one
#: prompt length, of the difference between the served path and this
#: reference, in logit units (natural log), on (a) the log-probability of
#: each emitted token and (b) how far the emitted token's reference logit
#: lies under the reference's largest.  Two limits, by what the streams
#: exercise (:func:`tolerance`): ``TOLERANCE`` where contexts pass ``topk``
#: and keys are dropped, ``TOLERANCE_DENSE`` where every key is selected
#: (plain causal GQA).
#:
#: Why a quartile (kind ``glm4_moe_lite``'s reason, twice over).  Top-8 of
#: 128 routing is discontinuous: where the 8th and 9th router probabilities
#: lie closer than the served path's bf16 rounding moves them, another
#: expert runs and that token's logits move by far more than rounding
#: moves them.  And so is the selection: the served path keeps its index
#: keys in bf16 and scores bf16 queries against them, so among the keys
#: within rounding of the 2,048th score a few enter or leave ``S_t``.  Past
#: 2,048 keys the attention weights over ``S_t`` are near-uniform with
#: seeded weights (each ~1/2,048), so a swapped key moves a token's logits
#: by little, and it moves single tokens; a WRONG set (another window,
#: another row's set) or a loss of precision moves every token, the best
#: quarter of them too.
#:
#: Why two limits.  Under ``topk`` keys the error is rounding and flipped
#: experts alone; past it the swapped keys come on top, and a sum over
#: 2,048 near-uniform weights averages a narrower K/V store's rounding
#: away.  So the long streams are where a wrong selection shows (and a
#: narrower store shows less), the short streams where a narrower store
#: shows.
#:
#: Their sizes, from readings on the v5e at the published widths (PR 34's
#: chip runs, PERF.md section 6), the lower quartile of ``logprob_err``
#: after the 24-token prompts / after the 5,000-token prompts: see
#: TOLERANCE_READINGS.  ``bf16`` and ``fp8_kv`` are of REFERENCE_STREAMS
#: streams together (18 and 5 seeds; of the long streams three together,
#: and four on three seeds read 0.0068-0.0096), the others of one stream
#: (one seed), where every token is wrong alike.
#: ``fp8_kv`` must fail and does on the short streams (on the long ones
#: only on some seeds); ``newest_window`` on the long ones only (under
#: ``topk`` it IS the selection); ``one_selection_a_chunk`` on both; an fp8
#: indexer (``fp8_index``: index queries and keys rounded to e4m3) is
#: reported without a verdict asked: one long stream read at the limit.
TOLERANCE = 0.025
TOLERANCE_DENSE = 0.008
TOLERANCE_READINGS = {
    "bf16": "0.0021-0.0044 / 0.0061-0.0135",
    "bf16_one_stream": "0.0011-0.0300 / 0.0038-0.0203",
    "newest_window": "0.0018 / 2.386",
    "one_selection_a_chunk": "0.851 / 0.934",
    "fp8_kv": "0.0098-0.0119 / 0.0117-0.0190",
    "fp8_kv_one_stream": "0.0040-0.0221 / 0.0080-0.0301",
    "fp8_index": "0.0018 / 0.0244",
}
QUANTILE = 0.25


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _layernorm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _rope(x, positions, theta):
    """x (T, H, D); rotate-half convention over all of D."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def index_scores(a, b, c, q_chunk: int, kv_chunk: int):
    """``I (T, T)`` float32 of index queries ``a (T, Hi, Di)``, index keys
    ``b (T, Di)`` and head weights ``c (T, Hi)``, in tiles of ``q_chunk`` x
    ``kv_chunk``; keys after a query read ``-inf``."""
    with jax.default_matmul_precision("highest"):
        t, hi, di = a.shape
        scale = (hi * di) ** -0.5
        rows = []
        for s in range(0, t, q_chunk):
            e = min(s + q_chunk, t)
            cols = []
            for ks in range(0, t, kv_chunk):
                ke = min(ks + kv_chunk, t)
                dots = jnp.einsum("qhd,kd->qhk", a[s:e], b[ks:ke])
                cols.append(scale * jnp.einsum(
                    "qh,qhk->qk", c[s:e], jax.nn.relu(dots)))
            rows.append(jnp.concatenate(cols, axis=1))
        pos = jnp.arange(t)
        return jnp.where(pos[:, None] >= pos[None, :],
                         jnp.concatenate(rows, axis=0), -jnp.inf)


def selection(scores, topk: int):
    """``S (T, T)`` bool from ``I``: row ``t`` keeps its ``min(t + 1,
    topk)`` largest keys at or before it, by an exact ``top_k`` (ties to
    the lower key)."""
    t = scores.shape[0]
    k = min(topk, t)
    vals, idx = jax.lax.top_k(scores, k)
    chosen = jnp.zeros((t, t), bool).at[
        jnp.arange(t)[:, None], idx].set(vals > -jnp.inf)
    return chosen


@partial(jax.jit, static_argnames=("eps", "theta", "n_heads", "n_kv_heads",
                                   "head_dim", "index_heads", "index_dim",
                                   "topk", "q_chunk", "kv_chunk", "block"))
def _attention(x, p, *, eps, theta, n_heads, n_kv_heads, head_dim,
               index_heads, index_dim, topk, q_chunk, kv_chunk, block):
    """``x + attention(norm(x))`` over the whole sequence x (T, d)."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t = x.shape[0]
        pos = jnp.arange(t)
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        qkv = h @ p["wqkv"].astype(f32)
        nq, nk = n_heads * head_dim, n_kv_heads * head_dim
        q = qkv[:, :nq].reshape(t, n_heads, head_dim)
        k = qkv[:, nq:nq + nk].reshape(t, n_kv_heads, head_dim)
        v = qkv[:, nq + nk:].reshape(t, n_kv_heads, head_dim)
        q = _rope(_rmsnorm(q, p["q_norm"]["scale"], eps), pos, theta)
        k = _rope(_rmsnorm(k, p["k_norm"]["scale"], eps), pos, theta)
        ix = p["indexer"]
        a = _rope((h @ ix["wq"].astype(f32)).reshape(
            t, index_heads, index_dim), pos, theta)
        b = _rope(_layernorm(h @ ix["wk"].astype(f32), ix["k_norm"]["scale"],
                             ix["k_norm"]["bias"], eps)[:, None, :],
                  pos, theta)[:, 0]
        c = h @ ix["ww"].astype(f32)
        chosen = selection(index_scores(a, b, c, q_chunk, kv_chunk), topk)
        group = n_heads // n_kv_heads
        kk = jnp.repeat(k, group, axis=1)
        vv = jnp.repeat(v, group, axis=1)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = (jnp.einsum("qhd,khd->hqk", q[s:e], kk[:e])
                      / np.sqrt(head_dim))
            probs = jax.nn.softmax(
                jnp.where(chosen[None, s:e, :e], scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", probs, vv[:e])
                        .reshape(e - s, -1))
        return x + jnp.concatenate(outs, 0) @ p["wo"].astype(f32)


@jax.jit
def _swiglu(h, gate, up, down):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        return (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) \
            @ down.astype(f32)


@partial(jax.jit, static_argnames=("eps", "top_k", "norm"))
def _route(x, ln2, router, *, eps, top_k, norm):
    """``(norm(x), chosen (T, k), weights (T, k))``."""
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, ln2, eps)
        probs = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
        # the k largest, by a stable sort: ties go to the lower id
        chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :top_k]
        w = jnp.take_along_axis(probs, chosen, axis=-1)
        if norm:
            w = w / w.sum(-1, keepdims=True)
        return h, chosen, w


@jax.jit
def _add_expert(out, h, idx, wts, w13, w2):
    """``out[idx] += wts * SwiGLU_e(h[idx])``; ``w13`` is ``[gate | up]``."""
    f = w2.shape[0]
    y = _swiglu(h[idx], w13[:, :f], w13[:, f:], w2)
    return out.at[idx].add(y * wts[:, None])


def _ffn(x, p, *, eps, top_k, norm):
    """``x + moe(norm(x))``: a loop over the experts."""
    m = p["moe"]
    h, chosen, w = _route(x, p["ln2"]["scale"], m["router"], eps=eps,
                          top_k=top_k, norm=norm)
    out = jnp.zeros_like(x)
    chosen, w = np.asarray(chosen), np.asarray(w)
    for e in range(m["router"].shape[-1]):       # one expert at a time
        rows, slot = np.nonzero(chosen == e)
        if rows.size == 0:
            continue
        # padded to a power of two with weight 0 (on row 0), so that the
        # jitted product compiles for a handful of sizes, not for every one
        n = max(8, 1 << int(rows.size - 1).bit_length())
        idx, wts = np.zeros(n, np.int32), np.zeros(n, np.float32)
        idx[:rows.size], wts[:rows.size] = rows, w[rows, slot]
        out = _add_expert(out, h, idx, wts, m["w13"][e], m["w2"][e])
    return x + out


@partial(jax.jit, static_argnames=("eps",))
def _head(x_last, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale, eps) @ lm_head.astype(jnp.float32)


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys."""
    sa = config["sa_config"]
    return dict(n_layers=int(config["num_hidden_layers"]),
                rms_norm_eps=float(config["rms_norm_eps"]),
                rope_theta=float(config["rope_theta"]),
                n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]),
                top_k=int(config["num_experts_per_tok"]),
                norm_topk_prob=bool(config["norm_topk_prob"]),
                index_heads=int(sa["indexer_num_heads"]),
                index_dim=int(sa["indexer_head_dim"]),
                index_topk=int(sa["topk"]),
                q_chunk=int(sa["q_chunk_size"]),
                kv_chunk=int(sa["kv_chunk_size"]))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, rms_norm_eps: float, rope_theta: float,
                n_heads: int, n_kv_heads: int, head_dim: int, top_k: int,
                norm_topk_prob: bool, index_heads: int, index_dim: int,
                index_topk: int, q_chunk: int, kv_chunk: int,
                block: int = 256) -> np.ndarray:
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    for i in range(n_layers):
        p = params[f"layer{i}"]
        x = _attention(x, {k: p[k] for k in (
            "ln1", "wqkv", "q_norm", "k_norm", "indexer", "wo")},
            eps=rms_norm_eps, theta=rope_theta, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim,
            index_heads=index_heads, index_dim=index_dim, topk=index_topk,
            q_chunk=q_chunk, kv_chunk=kv_chunk, block=block)
        x = _ffn(x, p, eps=rms_norm_eps, top_k=top_k, norm=norm_topk_prob)
    return np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                            params["lm_head"], eps=rms_norm_eps), np.float32)


def tolerance(context: int, index_topk: int, **_) -> float:
    """The limit for a stream whose longest context is ``context`` keys."""
    return TOLERANCE if context > index_topk else TOLERANCE_DENSE


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 **hyper) -> Dict[str, np.ndarray]:
    """One served greedy stream against the reference: one forward over
    ``prompt + tokens[:-1]``, whose last ``len(tokens)`` logit rows predict
    ``tokens``.  Per token: ``logprob_err``, the served log-probability
    against the reference's, and ``argmax_gap``, the reference's largest
    logit minus its logit of the emitted token."""
    n = len(tokens)
    logits = last_logits(params, list(prompt) + list(tokens[:-1]), n, **hyper)
    logits = logits.astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows = np.arange(n)
    toks = np.asarray(tokens)
    return {"logprob_err": np.abs(logp[rows, toks] - np.asarray(logprobs)),
            "argmax_gap": logits.max(-1) - logits[rows, toks]}


def summary(streams: Sequence[Dict[str, np.ndarray]]) -> Dict[str, float]:
    """The tokens of ``streams`` (:func:`token_errors` of each) judged
    together.  ``logprob_err`` and ``argmax_gap`` are the LOWER QUARTILES
    over all the tokens (what TOLERANCE judges, and why);
    ``logprob_err_median``, ``logprob_err_max`` and ``flipped_share``
    (tokens whose error is past 0.05, as a flipped expert makes it) are
    reported beside them and judge nothing."""
    err = np.concatenate([s["logprob_err"] for s in streams])
    gap = np.concatenate([s["argmax_gap"] for s in streams])
    return {
        "logprob_err": float(np.quantile(err, QUANTILE)),
        "argmax_gap": float(np.quantile(gap, QUANTILE)),
        "logprob_err_median": float(np.median(err)),
        "logprob_err_max": float(err.max()),
        "flipped_share": float((err > 0.05).mean()),
    }


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """:func:`summary` of one stream alone."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
