"""Plain reference: Xing4.0-29B-A4B (``model_type`` ``xing4_0``).

Written from the published ``config.json``, the DeepSeek-V3-style layer its
keys name (latent attention, sigmoid-routed experts, YaRN) and the two papers
its ``hc_*`` / ``mhc_*`` keys name (Hyper-Connections, arXiv:2409.19606;
manifold-constrained hyper-connections, arXiv:2512.24880); straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernel, no cache, no batching, no grouped product, nothing imported from
the program.

A token's residual state is ``n = hc_mult`` streams ``X (n, C)``, ``C =
hidden_size``.  ``X_0`` is ``n`` copies of the token's embedding row; after
the last layer ``logits = RMSNorm(sum_i X[i]) W_head``.  A layer is two
SUBLAYERS, latent attention then the FFN, each wrapped the same way
(:func:`hyper_connection`, :func:`write_back`) with its own parameters
(``phi (n C, 2 n + n^2)`` = ``[pre | post | res]``, ``alpha (3,)``, ``bias (2
n + n^2,)``, a norm scale ``g (n C,)``)::

    v      = RMSNorm_g(vec(X))                  over all n C values, hc_eps
    h_pre  = sigmoid(a_pre (v phi_pre) + b_pre)                       (n,)
    h_post = 2 sigmoid(a_post (v phi_post) + b_post)                  (n,)
    S      = clip(a_res mat(v phi_res) + b_res, clamp_min, clamp_max) (n, n)
    M      = exp(S); hc_sinkhorn_iters times:
               M <- M / (rowsum(M) + hc_eps); M <- M / (colsum(M) + hc_eps)
    u      = sum_i h_pre[i] X[i]                                      (C,)
    f      = F(RMSNorm(u))          the sublayer's own norm, rms_norm_eps
    X'     = M X + outer(h_post, f)

*Latent attention* (:func:`attention`), in the **expanded** form as published
(the program serves the absorbed form from a latent cache): ``c_q = norm(x
W_qa)``; ``q = c_q W_qb`` -> per head ``[q_nope ; q_rope]``; ``[c_kv ; k_r] =
x W_kva``; ``c_kv = norm(c_kv)``; per head ``k_nope = c_kv W_uk^T``, ``v =
c_kv W_uv``; RoPE on ``q_rope`` and on ``k_r`` (one for all heads) at YaRN's
frequencies (:func:`yarn_inv_freq`): pair ``j`` of ``d = qk_rope_head_dim``
turns at ``theta^(-2j/d) ((1 - ramp_j) + ramp_j / factor)``, ``ramp_j =
clip((j - low) / (high - low), 0, 1)``, ``low = floor(corr(beta_fast))``,
``high = ceil(corr(beta_slow))``, ``corr(r) = d ln(L0 / (2 pi r)) / (2 ln
theta)``; cos and sin times ``mscale(s, mscale) / mscale(s, mscale_all_dim)``
(1 here); causal softmax over ``q . [k_nope ; k_r]`` times ``(nope +
rope)^-0.5 mscale(s, mscale_all_dim)^2``, ``mscale(s, m) = 0.1 m ln s + 1``.

*FFN* (:func:`ffn`): the first ``first_k_dense_replace`` layers SwiGLU; the
others ``s = sigmoid(x W_g)`` in float32, the ``num_experts_per_tok`` largest
of ``s + e_score_correction_bias`` chosen (ties to the lower id), weights
``s_chosen / sum(s_chosen) * routed_scaling_factor``, SwiGLU experts in a
**loop over the experts**, each upcast alone and applied to the rows that
chose it, plus the shared expert ungated.

It is handed the weights in the program's tree (bf16; ``tpulab/models/
spec.py`` documents it: ``w_uk`` / ``w_uv`` the halves of the published
``kv_b_proj``, an expert's ``w13[e]`` = ``[gate | up]``, a sublayer's
hyper-connection under ``hc_attn`` / ``hc_ffn``) EXCEPT ``wq_b``, which is
the published ``q_b_proj``: the program folds YaRN's softmax factor into it
at load, so that fold is part of what is compared.  Departures, shared with
the program and stated in the configuration file: the prediction layer
(``num_nextn_predict_layers``) is not built; RoPE in the rotate-half
convention (a column permutation of the published interleaved one); random
weights emit no EOS.  What the published keys do not settle is listed under
``assumed`` there.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream, and independent streams
#: (prompts drawn apart) a prompt length, their tokens judged TOGETHER: kind
#: ``keye_vl2``'s construction and reasons (a greedy stream on seeded weights
#: settles on one token and so carries one error).
REFERENCE_STEPS = 32
REFERENCE_STREAMS = 4
QUANTILE = 0.25

#: Largest LOWER QUARTILE, over the emitted tokens of the streams of one
#: prompt length, of the difference between the served path and this
#: reference, in logit units (natural log), on (a) the log-probability of
#: each emitted token and (b) how far the emitted token's reference logit
#: lies under the reference's largest.
#:
#: Why a quartile: top-4 of 64 routing is discontinuous (kind
#: ``glm4_moe_lite``'s reason): where the 4th and 5th of ``s + b`` lie closer
#: than the served path's bf16 rounding moves them the served path runs
#: another expert than the float32 reference, and that token's logits move
#: by several times what rounding alone moves them.  A loss of precision
#: moves EVERY token, the best quarter of them too.
#:
#: Its size, from two readings on the v5e at the published widths (PERF.md
#: section 6, PR 50; TOLERANCE_READINGS, each "prompts of 24 / prompts of
#: 2,000", four to twenty-four streams a length): bf16 as served must pass
#: and the latent store rounded to fp8 (e4m3; the nearest precision below
#: the one the configuration states) must fail, and does on both lengths.
#: A limit a length (:func:`tolerance`), because every variant reads 1.5-2.4
#: x higher behind 24 keys than behind 2,000; each the geometric middle of
#: its two readings: 2.1 x the largest bf16 reading and 0.46 of the smallest
#: fp8 one (short), 2.0 x and 0.49 (long).  The hyper-connections'
#: coefficients computed in bf16 (``bf16_coefficients``) are reported WITHOUT
#: a verdict: they read 1.05-1.2 x (short) and 1.3-1.7 x (long) the same
#: seed's bf16 reading, inside the spread of bf16 over seeds (1.9-2 x).  The
#: streams are held in bf16, so a map rounded to bf16 adds to every sublayer
#: the rounding its output gets anyway; no limit with room on both sides
#: lies between the two (PERF.md section 7).
TOLERANCE = 0.013
TOLERANCE_SHORT = 0.027
#: prompts under this many tokens are judged by TOLERANCE_SHORT
SHORT_PROMPT = 256
TOLERANCE_READINGS = {
    "bf16": "0.0065-0.0130 / 0.0035-0.0066 (sixteen seeds)",
    "fp8_latent": "0.0587-0.0734 / 0.0268-0.0481 (two seeds)",
    "bf16_coefficients": "0.0080-0.0148 / 0.0065-0.0078 (four seeds; no "
                         "verdict)",
}


def tolerance(prompt_len: int) -> float:
    """The limit on the lower quartiles of a prompt length's streams."""
    return TOLERANCE_SHORT if prompt_len < SHORT_PROMPT else TOLERANCE


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def yarn_mscale(factor: float, m: float) -> float:
    """``0.1 m ln(factor) + 1`` (1 where ``factor <= 1``)."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_bounds(d: int, theta: float, original: float, beta_fast: float,
                beta_slow: float):
    """``(low, high)``: the pairs below ``low`` keep their frequency, those
    above ``high`` are interpolated whole."""
    def corr(turns):
        return d * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))
    clip = lambda v: min(max(v, 0), d // 2 - 1)
    return clip(math.floor(corr(beta_fast))), clip(math.ceil(corr(beta_slow)))


def yarn_inv_freq(d: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``d / 2`` inverse frequencies of RoPE under YaRN, float64."""
    low, high = yarn_bounds(d, theta, original, beta_fast, beta_slow)
    j = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return theta ** (-2.0 * j / d) * ((1.0 - ramp) + ramp / factor)


def _rope(x, positions, inv_freq, mscale):
    """x (T, H, D); rotate-half convention over all of D at ``inv_freq``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = mscale * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = mscale * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@partial(jax.jit, static_argnames=("iters", "eps", "clamp"))
def hyper_connection(X, hc, *, iters, eps, clamp):
    """The read side of one sublayer: ``X (T, n, C)`` -> ``(u (T, C), M (T,
    n, n), h_post (T, n))``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t, n, _c = X.shape
        v = _rmsnorm(X.reshape(t, -1), hc["norm"]["scale"], eps)
        proj = v @ hc["phi"].astype(f32)
        a, b = hc["alpha"].astype(f32), hc["bias"].astype(f32)
        h_pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
        S = jnp.clip(a[2] * proj[:, 2 * n:] + b[2 * n:], clamp[0], clamp[1])
        M = jnp.exp(S.reshape(t, n, n))
        for _ in range(iters):
            M = M / (M.sum(axis=2, keepdims=True) + eps)
            M = M / (M.sum(axis=1, keepdims=True) + eps)
        return jnp.einsum("tn,tnc->tc", h_pre, X), M, h_post


@jax.jit
def write_back(X, M, h_post, f):
    """``X' = M X + outer(h_post, f)``."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("tij,tjc->tic", M, X) + h_post[:, :, None] * f[
            :, None, :]


@partial(jax.jit, static_argnames=("eps", "scale", "mscale", "block"))
def attention(u, p, inv_freq, *, eps, scale, mscale, block):
    """``attention(norm(u))`` over the whole sequence ``u (T, C)``, expanded:
    the sublayer's output, no residual."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        w_uk, w_uv = p["w_uk"].astype(f32), p["w_uv"].astype(f32)
        n_heads, nope, c = w_uk.shape
        t = u.shape[0]
        h = _rmsnorm(u, p["ln1"]["scale"], eps)
        cq = _rmsnorm(h @ p["wq_a"].astype(f32), p["q_norm"]["scale"], eps)
        q = (cq @ p["wq_b"].astype(f32)).reshape(t, n_heads, -1)
        kva = h @ p["wkv_a"].astype(f32)
        ckv = _rmsnorm(kva[:, :c], p["kv_norm"]["scale"], eps)
        pos = jnp.arange(t)
        k_r = _rope(kva[:, None, c:], pos, inv_freq, mscale)   # (T, 1, rope)
        q_rope = _rope(q[..., nope:], pos, inv_freq, mscale)
        # expanded keys and values, every head its own
        k_nope = jnp.einsum("tc,hnc->thn", ckv, w_uk)          # (T, H, nope)
        v = jnp.einsum("tc,hcv->thv", ckv, w_uv)               # (T, H, v)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (t, n_heads, k_r.shape[-1]))], -1)
        qf = jnp.concatenate([q[..., :nope], q_rope], -1)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = jnp.einsum("qhd,khd->hqk", qf[s:e], k[:e]) * scale
            mask = pos[s:e, None] >= pos[None, :e]
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                   axis=-1)
            outs.append(jnp.einsum("hqk,khv->qhv", probs, v[:e])
                        .reshape(e - s, -1))
        return jnp.concatenate(outs, 0) @ p["wo"].astype(f32)


@jax.jit
def _matmul(h, w):
    """One matrix, upcast alone."""
    with jax.default_matmul_precision("highest"):
        return h @ w.astype(jnp.float32)


def swiglu(h, gate, up, down):
    return _matmul(jax.nn.silu(_matmul(h, gate)) * _matmul(h, up), down)


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, *, eps):
    return _rmsnorm(x, scale, eps)


@partial(jax.jit, static_argnames=("top_k", "scale", "norm"))
def route(h, router, bias, *, top_k, scale, norm):
    """``(chosen (T, k), weights (T, k))`` of normed rows ``h``."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
        # the k largest of s + b, by a stable sort: ties go to the lower id
        chosen = jnp.argsort(-(s + bias.astype(jnp.float32)), axis=-1,
                             stable=True)[:, :top_k]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if norm:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return chosen, w * scale


@jax.jit
def _add_expert(out, h, idx, wts, w13, w2):
    """``out[idx] += wts * SwiGLU_e(h[idx])``; ``w13`` is ``[gate | up]``."""
    f = w2.shape[0]
    y = swiglu(h[idx], w13[:, :f], w13[:, f:], w2)
    return out.at[idx].add(y * wts[:, None])


def ffn(u, p, *, eps, top_k, scale, norm):
    """``ffn(norm(u))``, no residual; an expert layer where ``p`` has
    ``moe``."""
    h = _norm(u, p["ln2"]["scale"], eps=eps)
    if "moe" not in p:
        return swiglu(h, p["w1"], p["w3"], p["w2"])
    m, sh = p["moe"], p["shared"]
    chosen, w = route(h, m["router"], m["bias"], top_k=top_k, scale=scale,
                      norm=norm)
    out = swiglu(h, sh["w1"], sh["w3"], sh["w2"])
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
    return out


#: what :func:`attention` reads of a layer
ATTENTION_LEAVES = ("ln1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                    "w_uk", "w_uv", "wo")


def layer(X, p, inv_freq, *, hc, attn, moe):
    """One layer over the streams ``X (T, n, C)``: the attention sublayer,
    then the FFN sublayer; ``hc``, ``attn``, ``moe`` the keywords of
    :func:`hyper_connection`, :func:`attention`, :func:`ffn`."""
    u, M, h_post = hyper_connection(X, p["hc_attn"], **hc)
    X = write_back(X, M, h_post, attention(
        u, {k: p[k] for k in ATTENTION_LEAVES}, inv_freq, **attn))
    u, M, h_post = hyper_connection(X, p["hc_ffn"], **hc)
    return write_back(X, M, h_post, ffn(u, p, **moe))


@partial(jax.jit, static_argnames=("eps",))
def _head(X_last, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(X_last.sum(axis=1), scale, eps) @ lm_head.astype(
            jnp.float32)


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys."""
    ys = config["rope_scaling"]
    return dict(
        n_layers=int(config["num_hidden_layers"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        rope_dim=int(config["qk_rope_head_dim"]),
        nope_dim=int(config["qk_nope_head_dim"]),
        yarn=(float(ys["factor"]),
              float(ys["original_max_position_embeddings"]),
              float(ys["beta_fast"]), float(ys["beta_slow"]),
              float(ys["mscale"]), float(ys["mscale_all_dim"])),
        hc_mult=int(config["hc_mult"]),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_clamp=(float(config["mhc_h_res_clamp_min"]),
                  float(config["mhc_h_res_clamp_max"])),
        top_k=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, rms_norm_eps: float, rope_theta: float,
                rope_dim: int, nope_dim: int, yarn, hc_mult: int,
                hc_sinkhorn_iters: int, hc_eps: float, hc_clamp,
                top_k: int, routed_scaling_factor: float,
                norm_topk_prob: bool, block: int = 256) -> np.ndarray:
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``."""
    factor, original, fast, slow, m, m_all = yarn
    inv_freq = jnp.asarray(yarn_inv_freq(rope_dim, rope_theta, factor,
                                         original, fast, slow), jnp.float32)
    attn = dict(eps=rms_norm_eps, block=block,
                scale=(nope_dim + rope_dim) ** -0.5
                * yarn_mscale(factor, m_all) ** 2,
                mscale=yarn_mscale(factor, m) / yarn_mscale(factor, m_all))
    hc = dict(iters=hc_sinkhorn_iters, eps=hc_eps, clamp=tuple(hc_clamp))
    moe = dict(eps=rms_norm_eps, top_k=top_k, scale=routed_scaling_factor,
               norm=norm_topk_prob)
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    e = params["embed"][toks].astype(jnp.float32)
    X = jnp.broadcast_to(e[:, None, :], (e.shape[0], hc_mult, e.shape[1]))
    for i in range(n_layers):
        X = layer(X, params[f"layer{i}"], inv_freq, hc=hc, attn=attn, moe=moe)
    return np.asarray(_head(X[-n_last:], params["final_norm"]["scale"],
                            params["lm_head"], eps=rms_norm_eps), np.float32)


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 **hyper) -> Dict[str, np.ndarray]:
    """A served greedy stream against ONE forward over ``prompt +
    tokens[:-1]``, whose last ``len(tokens)`` logit rows predict ``tokens``:
    per token, ``err`` (the served log-probability against the reference's)
    and ``gap`` (the reference's largest logit minus its logit of the
    emitted token)."""
    n = len(tokens)
    logits = last_logits(params, list(prompt) + list(tokens[:-1]), n,
                         **hyper).astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows, toks = np.arange(n), np.asarray(tokens)
    return {"err": np.abs(logp[rows, toks] - np.asarray(logprobs)),
            "gap": logits.max(-1) - logits[rows, toks]}


def summary(streams: Sequence[Dict[str, np.ndarray]]) -> Dict[str, float]:
    """The streams of one prompt length judged together: ``logprob_err`` and
    ``argmax_gap`` are the LOWER QUARTILES over all their tokens (what
    TOLERANCE judges, and why); the median, the largest and the share of
    tokens past 0.05 (as a flipped expert makes it) judge nothing."""
    err = np.concatenate([s["err"] for s in streams])
    gap = np.concatenate([s["gap"] for s in streams])
    return {"logprob_err": float(np.quantile(err, QUANTILE)),
            "argmax_gap": float(np.quantile(gap, QUANTILE)),
            "logprob_err_median": float(np.median(err)),
            "logprob_err_max": float(err.max()),
            "flipped_share": float((err > 0.05).mean())}


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """One stream alone (the tests' form)."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
