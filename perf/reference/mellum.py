"""Plain reference: Mellum2-12B-A2.5B (``model_type`` ``mellum``).

Written from the published ``config.json`` and the conventions of the
Qwen3-MoE lineage its keys come from; straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: ONE causal forward
over the whole sequence, the window as a MASK over the full score matrix
(computed in blocks of query rows so that it fits), the experts in a loop;
no kernel, no cache, no page table, no batching, no grouped product, nothing
imported from the program.

``N(.)`` is RMSNorm at ``rms_norm_eps``.  A layer over the residual ``x (T,
C)``, ``H`` query heads on ``G`` KV heads of ``D`` (32 on 4 of 128), ``h =
N(x)``::

    q, k, v = h W_q, h W_k, h W_v            head i of q reads KV head i // (H / G)
    q, k    = N_head(q), N_head(k)           RMSNorm over each head's D columns
    full layer    (``layer_types[l] == "full_attention"``):
        inv_j  = YaRN(theta, factor, original, beta_fast, beta_slow)   (below)
        q, k   = f * RoPE(q, inv), f * RoPE(k, inv)     f = attention_factor,
                                                        on cos AND sin
        seen   = {j : j <= i}
    window layer  (``"sliding_attention"``):
        q, k   = RoPE(q, theta^(-2j / D)), RoPE(k, ...)  no factor
        seen   = {j : i - sliding_window < j <= i}       1,024 keys, the
                                                         row's own among them
    o       = softmax over seen of (q . k D^-0.5) v      float32
    x       = x + o W_o

    h       = N(x);  p = softmax(h W_r) over the 64 columns, float32
    S       = the 8 largest of p (ties to the lower id);  w_e = p_e / sum_S p
    x       = x + sum_{e in S} w_e W_dn,e (silu(W_g,e h) * W_up,e h)

then ``logits = N(x) W_head`` (the head is a matrix of its own).  YaRN's
table, pair ``j`` of ``D / 2``: ``lo = floor(c(beta_fast))``, ``hi =
ceil(c(beta_slow))`` with ``c(t) = D ln(original / (2 pi t)) / (2 ln
theta)`` (both cut to ``[0, D - 1]``), ``ramp_j = clip((j - lo) / (hi - lo),
0, 1)``, ``inv_j = theta^(-2j / D) ((1 - ramp_j) + ramp_j / factor)``: the
pairs that turn more than ``beta_fast`` times within the original context
keep their frequency, those that turn less than ``beta_slow`` times have it
divided by ``factor``.

It is handed the weights in the program's tree (bf16; ``tpulab/models/
spec.py`` documents it: ``wqkv`` = ``[q | k | v]``, ``q_norm`` / ``k_norm``
``{"scale": (D,)}``, an expert's ``w13[e]`` = ``[gate | up]``).

Departures from the published description, shared with the program and
stated in the configuration file:

* the per-head q/k norm is ``assumed``: the config has no key for it (its
  keys are the Qwen3-MoE lineage's, whose attention norms q and k a head);
* the window's edge is the Hugging Face mask's (``kv > q - sliding_window``);
* RoPE in the rotate-half convention (pairs ``(j, j + D / 2)``), the
  lineage's;
* the catalog's ``described_as`` names an "MTP head": ``config`` has no key
  for one, and none is built here or in the program;
* random weights emit no EOS.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream, and independent streams
#: (prompts drawn apart) a prompt length, their tokens judged TOGETHER: kind
#: ``keye_vl2``'s construction and reasons (top-8 of 64 routing is
#: discontinuous: where the 8th and 9th router probabilities lie closer than
#: the served path's bf16 rounding moves them, the served path runs another
#: expert than the float32 reference and that token's logits move by several
#: times what rounding alone moves them; a loss of precision moves EVERY
#: token, the best quarter of them too).
REFERENCE_STEPS = 32
REFERENCE_STREAMS = 4
QUANTILE = 0.25

#: Limits, each with its readings on the v5e at the published widths through
#: the Generate RPC under the cell's engine (TOLERANCE_READINGS, each "after
#: the 24-token prompts / after the 9,000-token prompts", four streams a
#: length over eight seeds for bf16, two streams a length on one seed for a
#: fault: my chip runs, PR 56; PERF.md section 6).  Six numbers of every
#: prompt length, each under a limit; the next precision below the
#: configuration's (the K/V pages in fp8, e4m3) and the three faults this
#: model adds each fail by at least one of them, and none fails by each.
#:
#: ``logprob_err`` / ``argmax_gap``: the LOWER QUARTILE over the emitted
#: tokens of a length's streams of the served log-probability against the
#: reference's (and of how far the emitted token's reference logit lies
#: under the reference's largest), a limit a length (:func:`tolerance`).
#: Long prompts: the geometric middle of bf16's largest reading and fp8
#: pages' (2.4 x the one, 0.40 of the other; a window whose edge lies a
#: page off reads 0.0204 and fails it too).  Short prompts: 2.7 x bf16's
#: largest, and there it does NOT part fp8 pages (0.0078: behind 24 keys a
#: rounded row moves a logit little): ``kv_err`` does; plain RoPE on the
#: full layers reads 0.0184.
#:
#: The stores, read where the server holds them once a stream has ended
#: (BOTH groups: a full layer's rows at every position, a window layer's
#: from the first row its table still held, position 7,936 behind the long
#: prompts), a row's error its difference over the row's norm, the larger
#: of the key's and the value's:
#:
#: ``kv_err``: the MEDIAN row of layer 0 (a window layer, which no router
#: and no attention reaches): a store kept one precision lower moves every
#: row (bf16 0.0029 / 0.0039 on every seed, fp8 pages 7-9 x that); the
#: geometric middle.
#: ``kv1_err``: the median row of LAYER 1, the first whose input went
#: through a window layer's attention, one router behind it: a window that
#: sees every key (1.25), or one whose edge lies a page off (0.26), moves
#: every row past the window there; under the window both faults are the
#: same model and read as bf16.  The geometric middle of bf16's largest and
#: the smallest other reading (fp8 pages' 0.046).
#: ``full_kv_err``: the median row of the FIRST FULL layer: plain RoPE where
#: YaRN's table and factor belong turns every key another way (0.22 / 0.83),
#: at BOTH lengths (the factor alone is 28 % of a key); the geometric middle
#: of bf16's largest and fp8 pages' smallest.
#: ``layers_kv_err``: the worst layer's median over the streams of its
#: median row, a limit a length (:func:`layers_tolerance`).  It is the ONLY
#: store number that reads layers 2 and 4 to 7 (``kv_err`` reads layer 0,
#: ``kv1_err`` layer 1, ``full_kv_err`` layer 3), and the logits pass fp8
#: pages there, so it is held against fp8 pages in THOSE layers alone
#: (``fp8_deep`` below: 0.0319 / 0.0316, a rounded layer's own rows and
#: little from the layers before it, where fp8 pages in every layer read
#: 0.0561 / 0.0663): the geometric middle of bf16's largest over fourteen
#: seeds (0.0125 / 0.0177: layer 7 / layer 2) and ``fp8_deep``'s, 1.6 x /
#: 1.36 x the one and 0.63 / 0.76 of the other.  At 0.027 / 0.034, the middle of
#: bf16 and fp8 pages in EVERY layer, ``fp8_deep`` passed every limit of
#: the long prompts and failed the short ones' by 18 % (the review round's
#: run through the harness).  A layer's rows in another layer's pages or in
#: the other group's read 1.4 (two independent rows).
TOLERANCE = 0.015
TOLERANCE_SHORT = 0.012
#: prompts under this many tokens are judged by the ``_SHORT`` limits
SHORT_PROMPT = 256
KV_TOLERANCE = 0.010
KV1_TOLERANCE = 0.021
FULL_KV_TOLERANCE = 0.024
LAYERS_TOLERANCE = 0.024
LAYERS_TOLERANCE_SHORT = 0.020
TOLERANCE_READINGS: Dict[str, str] = {
    "bf16": "logprob_err 0.0021-0.0044 / 0.0033-0.0062 (a stream alone up "
            "to 0.0078 / 0.0193); kv_err 0.00286-0.00288 / 0.00388-0.00390; "
            "kv1_err 0.0061-0.0063 / 0.0099-0.0100; full_kv_err "
            "0.0066-0.0096 / 0.0102-0.0105; layers_kv_err 0.0075-0.0125 / "
            "0.0148-0.0177 (eight seeds; the review round's six more read "
            "0.0082-0.0107 / 0.0153-0.0160); under load (four more streams "
            "of the long prompts among twelve other requests, eight "
            "callers; six seeds) logprob_err 0.0032-0.0059",
    "fp8_deep (fp8 pages in layers 2 and 4-7 alone, four streams a length "
    "through perf/run.py itself, two seeds)":
        "layers_kv_err 0.0319 / 0.0316 and 0.0315 / 0.0314 (layer 2 0.0278 "
        "/ 0.0316, layers 4-7 0.0297-0.0319 / 0.0292-0.0298); every other "
        "number under its limit: logprob_err 0.0047-0.0064 / 0.0032-0.0047, "
        "kv_err, kv1_err as bf16, full_kv_err 0.0105-0.0113 / 0.0103-0.0105",
    "fp8_pages": "logprob_err 0.0078 / 0.0377; kv_err 0.0270 / 0.0268; "
                 "kv1_err 0.0460 / 0.0624; full_kv_err 0.0558 / 0.0590; "
                 "layers_kv_err 0.0561 / 0.0663 (jax.lax.reduce_precision "
                 "to e4m3 at the scatter: a convert pair is dropped on the "
                 "chip); through perf/run.py itself, four streams a length: "
                 "layers_kv_err 0.0535 / 0.0706, kv_err 0.0267 / 0.0268, "
                 "logprob_err 0.0117 / 0.0165",
    "window_all (a window layer attends every key)":
        "as bf16 / logprob_err 3.42, argmax_gap 2.92, kv1_err 1.25, "
        "full_kv_err 1.31, layers_kv_err 1.31; kv_err as bf16",
    "edge_page (the window 1,040 keys: its edge a page off)":
        "as bf16 / logprob_err 0.0204, kv1_err 0.258, full_kv_err 0.0857, "
        "layers_kv_err 0.258; kv_err as bf16",
    "plain_rope (theta^(-2j/d) and no factor on the full layers)":
        "logprob_err 0.0184 / 0.222; full_kv_err 0.219 / 0.831; "
        "layers_kv_err 0.223 / 0.831; kv_err, kv1_err as bf16",
}


def tolerance(prompt_len: int) -> float:
    """The limit on the lower quartiles of a prompt length's streams."""
    return TOLERANCE_SHORT if prompt_len < SHORT_PROMPT else TOLERANCE


def layers_tolerance(prompt_len: int) -> float:
    """The limit on ``layers_kv_err`` of a prompt length's streams."""
    return (LAYERS_TOLERANCE_SHORT if prompt_len < SHORT_PROMPT
            else LAYERS_TOLERANCE)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies ``(D / 2,)``, float32 (the module
    docstring has the rule)."""
    d = head_dim

    def corr(turns):
        return d * np.log(original / (2 * np.pi * turns)) / (2 * np.log(theta))
    lo = max(int(np.floor(corr(beta_fast))), 0)
    hi = min(int(np.ceil(corr(beta_slow))), d - 1)
    j = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((j - lo) / ((hi - lo) or 1e-3), 0, 1)
    return (theta ** (-2 * j / d) * ((1 - ramp) + ramp / factor)).astype(
        np.float32)


def plain_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """``theta^(-2j / D)``, ``(D / 2,)`` float32."""
    j = np.arange(head_dim // 2, dtype=np.float64)
    return (theta ** (-2 * j / head_dim)).astype(np.float32)


def _rope(x, positions, inv, factor: float):
    """x (T, H, D): rotate-half by ``inv (D / 2,)``, cos and sin times
    ``factor``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None] * factor
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     -1) * sin


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "eps", "factor",
                                   "window", "block"))
def attention(x, ln, p, inv, *, n_heads, n_kv_heads, eps, factor, window,
              block):
    """``(x + Attn(N(x)) (T, C), the roped keys (T, G * D), the values (T, G
    * D))`` of one layer: ``window`` 0 a full layer, else the keys a row
    sees; the softmax over the FULL score row of each query, in blocks of
    ``block`` query rows."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t = x.shape[0]
        d = p["q_norm"]["scale"].shape[0]
        hq, g = n_heads, n_kv_heads
        h = _rmsnorm(x, ln, eps)
        qkv = h @ p["wqkv"].astype(f32)
        q = qkv[:, :hq * d].reshape(t, hq, d)
        k = qkv[:, hq * d:(hq + g) * d].reshape(t, g, d)
        v = qkv[:, (hq + g) * d:].reshape(t, g, d)
        q = _rmsnorm(q, p["q_norm"]["scale"], eps)
        k = _rmsnorm(k, p["k_norm"]["scale"], eps)
        pos = jnp.arange(t)
        q, k = _rope(q, pos, inv, factor), _rope(k, pos, inv, factor)
        qg = q.reshape(t, g, hq // g, d)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = jnp.einsum("qgrd,kgd->grqk", qg[s:e], k[:e]) * d ** -0.5
            seen = pos[s:e, None] >= pos[None, :e]
            if window:
                seen &= pos[None, :e] > pos[s:e, None] - window
            probs = jax.nn.softmax(jnp.where(seen[None, None], scores,
                                             -jnp.inf), axis=-1)
            outs.append(jnp.einsum("grqk,kgd->qgrd", probs, v[:e])
                        .reshape(e - s, -1))
        out = jnp.concatenate(outs, 0) @ p["wo"].astype(f32)
        return x + out, k.reshape(t, -1), v.reshape(t, -1)


@jax.jit
def _swiglu(h, gate, up, down):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        return (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) \
            @ down.astype(f32)


@partial(jax.jit, static_argnames=("eps", "top_k"))
def _route(x, ln2, router, *, eps, top_k):
    """``(N(x), chosen (T, k), weights (T, k))``."""
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, ln2, eps)
        probs = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
        # the k largest, by a stable sort: ties go to the lower id
        chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :top_k]
        w = jnp.take_along_axis(probs, chosen, axis=-1)
        return h, chosen, w / w.sum(-1, keepdims=True)


@jax.jit
def _add_expert(out, h, idx, wts, w13, w2):
    """``out[idx] += wts * SwiGLU_e(h[idx])``; ``w13`` is ``[gate | up]``."""
    f = w2.shape[0]
    y = _swiglu(h[idx], w13[:, :f], w13[:, f:], w2)
    return out.at[idx].add(y * wts[:, None])


def experts(x, p, *, eps, top_k):
    """``x + MoE(N(x))``: a loop over the experts, each upcast alone and
    applied to the rows that chose it."""
    m = p["moe"]
    h, chosen, w = _route(x, p["ln2"]["scale"], m["router"], eps=eps,
                          top_k=top_k)
    out = jnp.zeros_like(x)
    chosen, w = np.asarray(chosen), np.asarray(w)
    for e in range(m["router"].shape[-1]):
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
    rope = config["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
        raise ValueError("the reference is written for YaRN on the full "
                         "layers and plain RoPE on the window layers")
    d = int(config["head_dim"])
    return dict(
        # (a configuration cut in depth keeps the published list whole)
        layer_types=tuple(config["layer_types"])[
            :int(config["num_hidden_layers"])],
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        top_k=int(config["num_experts_per_tok"]),
        window=int(config["sliding_window"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        full_inv=tuple(yarn_inv_freq(
            d, float(full["rope_theta"]), float(full["factor"]),
            float(full["original_max_position_embeddings"]),
            float(full["beta_fast"]), float(full["beta_slow"])).tolist()),
        full_factor=float(full["attention_factor"]),
        window_inv=tuple(plain_inv_freq(
            d, float(slide["rope_theta"])).tolist()))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, layer_types: Sequence[str], n_heads: int, n_kv_heads: int,
                top_k: int, window: int, rms_norm_eps: float, full_inv,
                full_factor: float, window_inv, block: int = 256,
                stores: bool = False):
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``.  With ``stores`` also what a server
    holds of every layer once it has taken in every token: ``(logits, kv (L,
    2, T, G * D))``, the roped keys and the values, in layer order."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    rows = []
    for i, kind in enumerate(layer_types):
        p = params[f"layer{i}"]
        slides = kind == "sliding_attention"
        x, k, v = attention(
            x, p["ln1"]["scale"], p,
            np.asarray(window_inv if slides else full_inv, np.float32),
            n_heads=n_heads, n_kv_heads=n_kv_heads, eps=rms_norm_eps,
            factor=1.0 if slides else full_factor,
            window=window if slides else 0, block=block)
        if stores:
            rows.append(np.stack([np.asarray(k), np.asarray(v)]))
        x = experts(x, p, eps=rms_norm_eps, top_k=top_k)
    logits = np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                              params["lm_head"], eps=rms_norm_eps),
                        np.float32)
    return (logits, np.stack(rows)) if stores else logits


def store_errors(served: Dict[str, Any], want: np.ndarray,
                 layer_types: Sequence[str]) -> Dict[str, Any]:
    """What the server holds after a stream against what the reference would
    (``last_logits(..., stores=True)``).  ``served``: ``"full" (Lf, 2, T, G *
    D)`` the full layers' rows at every position, ``"window" (Lw, 2, T - t0,
    G * D)`` the window layers' rows from position ``"window_start"`` =
    ``t0`` on (what their group still held).  A row's error is its
    difference over the row's norm, the larger of the key's and the
    value's; ``layer_kv_err (L,)`` is each layer's MEDIAN row, in layer
    order, and ``kv_err``, ``kv1_err``, ``full_kv_err`` the layers the
    module's limits name."""
    t0 = int(served["window_start"])
    at = {"full": 0, "window": 0}
    medians = []
    for i, kind in enumerate(layer_types):
        group = "window" if kind == "sliding_attention" else "full"
        got = np.asarray(served[group][at[group]], np.float64)
        at[group] += 1
        ref = want[i].astype(np.float64)
        if group == "window":
            ref = ref[:, t0:]
        if got.shape != ref.shape:
            raise ValueError(f"layer {i}: served rows {got.shape} against "
                             f"the reference's {ref.shape}")
        off = (np.linalg.norm(got - ref, axis=-1)
               / np.linalg.norm(ref, axis=-1))              # (2, rows)
        medians.append(float(np.median(off, axis=-1).max()))
    kinds = list(layer_types)
    return {"layer_kv_err": np.asarray(medians),
            "kv_err": medians[0], "kv1_err": medians[1],
            "full_kv_err": medians[kinds.index("full_attention")]}


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 stores: Optional[Dict[str, Any]] = None,
                 **hyper) -> Dict[str, Any]:
    """A served greedy stream against ONE forward over ``prompt +
    tokens[:-1]``, whose last ``len(tokens)`` logit rows predict ``tokens``:
    per token, ``logprob_err`` (the served log-probability against the
    reference's) and ``argmax_gap`` (the reference's largest logit minus its
    logit of the emitted token).  With ``stores`` (what the server held of
    both groups once the stream had ended: every token of that forward taken
    in, and nothing else) also :func:`store_errors`, from the same
    forward."""
    n = len(tokens)
    fed = list(prompt) + list(tokens[:-1])
    logits = last_logits(params, fed, n, stores=stores is not None, **hyper)
    out: Dict[str, Any] = {}
    if stores is not None:
        logits, want = logits
        out = store_errors(stores, want, hyper["layer_types"])
    logits = logits.astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows, toks = np.arange(n), np.asarray(tokens)
    return dict(out,
                logprob_err=np.abs(logp[rows, toks] - np.asarray(logprobs)),
                argmax_gap=logits.max(-1) - logits[rows, toks])


def summary(streams: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """The streams of one prompt length judged together: ``logprob_err`` and
    ``argmax_gap`` are the LOWER QUARTILES over all their tokens (the
    median, the largest and the share of tokens past 0.05, as a flipped
    expert makes it, judge nothing); where the streams carry them,
    ``kv_err``, ``kv1_err`` and ``full_kv_err`` are the MEDIANS over the
    streams and ``layers_kv_err`` the worst layer's median over the
    streams."""
    err = np.concatenate([s["logprob_err"] for s in streams])
    gap = np.concatenate([s["argmax_gap"] for s in streams])
    out = {"logprob_err": float(np.quantile(err, QUANTILE)),
           "argmax_gap": float(np.quantile(gap, QUANTILE)),
           "logprob_err_median": float(np.median(err)),
           "logprob_err_max": float(err.max()),
           "flipped_share": float((err > 0.05).mean())}
    if all("layer_kv_err" in s for s in streams):
        for name in ("kv_err", "kv1_err", "full_kv_err"):
            out[name] = float(np.median([s[name] for s in streams]))
        out["layers_kv_err"] = float(np.median(
            np.stack([s["layer_kv_err"] for s in streams]), axis=0).max())
    return out


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """One stream alone (the tests' form)."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
