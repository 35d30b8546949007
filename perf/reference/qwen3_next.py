"""Plain reference: the decoder of Qwen3-Next-80B-A3B-Instruct (``model_type``
``qwen3_next``).

Written from the published ``config.json`` keys and the layers the model type
names (``Qwen3NextGatedDeltaNet``, ``Qwen3NextAttention``,
``Qwen3NextSparseMoeBlock`` of the published modelling code, as remembered:
there is no network here, so what the keys do not settle is listed under
``assumed`` in the configuration file); straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: no kernel, no
cache, no batching, no chunk form, no grouped product, nothing imported from
the program.

``N(.)`` is RMSNorm at ``rms_norm_eps`` in the zero-centred form ``x_hat * (1
+ w)``; the served weights hold ``scale = 1 + w``.  Layer ``i`` is attention
iff ``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet; every
layer's FFN is the expert block::

    x <- x + Mixer_i(N(x; input_layernorm))
    x <- x + MoE(N(x; post_attention_layernorm))

*Gated DeltaNet* (``Hk`` key heads, ``Hv`` value heads ``j`` on key head ``j
// (Hv / Hk)``, widths ``d_k``, ``d_v``; ``L2(.) = . / sqrt(sum .^2 +
1e-6)``): ``[q | k | v | z] = h W_qkvz`` and ``[b | a] = h W_ba`` (the served
weights hold each part's heads together: ``tpulab.models.spec.split_qkvz``
of the published matrices, whose columns go key head by key head); ``[q | k |
v]_t <- silu(sum_d w_d [q
| k | v]_{t-3+d})`` (depthwise, causal, ``linear_conv_kernel_dim`` taps, no
bias, zeros before the sequence; ``z`` is not convolved); ``beta =
sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``qh = L2(q)
d_k^-0.5``, ``kh = L2(k)``; per value head, ``S_{-1} = 0``::

    S <- exp(g_t) S;  d = beta_t (v_t - S^T kh_t);  S <- S + kh_t (x) d;  o_t = S^T qh_t

``Mixer = concat_j(RMSNorm(o_tj; w_norm) * silu(z_tj)) W_out``.  Here: ONE
sequential ``lax.scan`` over the tokens: the recurrence is the definition.

*Gated attention* (``H`` query heads on ``Hkv`` KV heads of ``head_dim``):
``[q_j | gate_j] = h W_q[j]``, ``k``, ``v``; ``q <- RoPE_r(N(q))``, ``k <-
RoPE_r(N(k))`` with the norms over the ``head_dim`` of a head and rotate-half
over the first ``r = partial_rotary_factor * head_dim`` columns; full causal
softmax at ``head_dim^-0.5``; ``Mixer = concat_j(o_j * sigmoid(gate_j)) W_o``.

*MoE*: ``p = softmax(h W_r)`` over ALL ``E`` experts in float32; the
``num_experts_per_tok`` largest are chosen (ties to the lower id), weighted
``p / sum over the chosen``; ``y = sum over the chosen experts HELD HERE`` of
``w_e W2_e(silu(W1_e h) * W3_e h)``, a loop over the held experts (``first ..
first + held``: what the absent ones would add is left out, as the served
program leaves it out), ``+ sigmoid(h w_g) Ws2(silu(Ws1 h) * Ws3 h)``.

Final norm, untied head (over the slice of the vocabulary held here).

It is handed the *served* weights (bf16, the program's layout, documented in
``tpulab/models/spec.py``) and upcasts one layer at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream, and streams asked at each
#: prompt length (prompts drawn apart, their tokens judged TOGETHER under
#: one lower quartile): kind ``keye_vl2``'s construction and its reasons.
#: Top-10 of 512 routing is discontinuous (a near tie between the 10th and
#: 11th probability runs another expert, and here only a quarter of the
#: experts add anything, so a flip can also add or drop a whole expert's
#: output), and a greedy stream on seeded weights settles on one or two ids,
#: so one stream carries one error.
REFERENCE_STEPS = 32
REFERENCE_STREAMS = 4
QUANTILE = 0.25

#: Largest LOWER QUARTILE, over the emitted tokens of the streams of one
#: prompt length, of the difference between the served path and this
#: reference, in logit units, on (a) the log-probability of each emitted
#: token and (b) how far the emitted token's reference logit lies under the
#: reference's largest.
#:
#: Its size, from TOLERANCE_READINGS (my chip runs, PR 39: on the v5e at the
#: published widths through the Generate RPC under the cell's engine; the
#: lower quartile of ``logprob_err`` after the 24-token prompts / after the
#: 2,000-token prompts, seven whole 256-token chunks and a tail; ``bf16``
#: over the seeds of every run made, the faults on one seed each).  bf16
#: serving reads 3-4 x what the other expert kinds read (0.002-0.009), and
#: not by its router: drawn ten times as wide the router gives the same
#: readings.  With seeded weights q and k of a Gated DeltaNet head are
#: nearly orthogonal, so ``q . k`` is a small difference of large terms that
#: carries the activations' bf16 rounding ~sqrt(128) times over, the output
#: is LINEAR in it (no softmax flattens it) and the head's RMSNorm brings it
#: back to full scale: six such layers, in float32 inside or not, put the
#: logits 0.008-0.018 from the reference (the same 0.010-0.014 on the CPU at
#: these widths with the mixer and the expert block computed in float32 on
#: bf16 inputs).  The limit lies between that band and the faults it must
#: catch, 1.7 x above the largest bf16 reading (26 seeds) and 1.5 x below
#: the smallest fault's: a state dropped at a chunk boundary fails on the long prompts
#: (the short ones are one chunk), RoPE over all 256 columns on the short
#: ones (over 2,000 keys attention is near-uniform and positions hardly
#: show), an ungated shared expert on both.  What it CANNOT catch: the
#: state rounded to bf16 after every dispatch and an fp8 (e4m3) K/V store
#: read inside bf16 serving's own band (a bf16 state adds ~0.3 % to an
#: output that already carries ~1 %; two of eight layers hold K/V, behind
#: an output gate).  Those two are the next precision below what the
#: configuration states, so the check also reads the STORES, each under a
#: limit of its own: STATE_TOLERANCE and KV_TOLERANCE below.
TOLERANCE = 0.03
TOLERANCE_READINGS: Dict[str, str] = {
    "bf16": "0.0081-0.0168 / 0.0101-0.0181",
    "dropped_state": "0.0142-0.0143 / 0.133-0.471",
    "rope_whole": "0.046-0.060 / 0.016-0.019",
    "ungated_shared": "0.63-0.74 / 0.45-0.51",
    "fp8_state": "0.71 / 0.63",
    "bf16_state": "0.011-0.014 / 0.011-0.016",
    "fp8_kv": "0.012-0.017 / 0.009-0.014",
    "router_drawn_10x_wider": "0.0130 / 0.0159",
}

#: What the server HOLDS once a stream has ended, against what this
#: reference holds after the same tokens (:func:`store_errors`; the median
#: over the streams of a prompt length): ``state_err`` of the FIRST Gated
#: DeltaNet layer's state and ``kv_err`` of the FIRST attention layer's key
#: and value rows.  The first of each, because a store's precision is one
#: for all its layers and the first carries least of what the layers before
#: it add: the state of layer 0 is a function of the embeddings alone (bf16
#: serving reads 0.0036-0.0038 there, on every stream, prompt length and
#: seed: the rounding of q, k and v to bf16 after their projection and
#: convolution; layers 1 and 2 read ~0.010 and ~0.017 on the CPU at these
#: widths), and the rows of layer 3 carry three layers' worth (0.017-0.020).
#: Each limit lies between bf16 serving's largest reading and the smallest
#: reading of the store kept one precision lower, STORE_READINGS (my chip
#: runs, PR 39: the v5e, the published widths, the cell's engine, after the
#: 24-token / the 2,000-token prompts; the faults on three seeds, ``bf16``
#: on eight): a state rounded to bf16 after every dispatch (eight rounds
#: and 31 steps a long stream: the roundings compound in the heads that
#: remember longest, so the reading follows the seed's ``A_log`` and
#: ``dt_bias``) reads 1.7-2.2 x bf16 serving, and rows rounded to e4m3
#: before they are scattered 1.6-1.9 x.  STATE_TOLERANCE is 1.31 x above the
#: one and 1.32 x below the other, KV_TOLERANCE 1.22 x and 1.32 x: narrow,
#: and enough, because bf16 serving's readings hardly move (the state's by
#: 7 % over everything read, a long stream's rows by 5 %; a SHORT stream's
#: rows by 0.015-0.029 a stream, 55 rows of which 31 are one repeated
#: token, which is why the median stream is judged: 0.0170-0.0196).
#: Neither store's fault moves the other's number, nor TOLERANCE's.
STATE_TOLERANCE = 0.005
KV_TOLERANCE = 0.024
STORE_READINGS: Dict[str, Dict[str, str]] = {
    "state_err": {"bf16": "0.00356-0.00360 / 0.00361-0.00383",
                  "bf16_state": "0.00658-0.00720 / 0.00689-0.00826",
                  "fp8_kv": "0.00356-0.00359 / 0.00361-0.00382"},
    "kv_err": {"bf16": "0.0170-0.0196 / 0.0172-0.0180",
               "bf16_state": "0.0177-0.0197 / 0.0174-0.0182",
               "fp8_kv": "0.0316-0.0328 / 0.0320-0.0324"},
}


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta, rot):
    """x (T, H, D): rotate-half over the first ``rot`` columns, the rest
    pass."""
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    r = x[..., :rot]
    turned = r * cos + jnp.concatenate([-r[..., half:], r[..., :half]],
                                       -1) * sin
    return jnp.concatenate([turned, x[..., rot:]], -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token at a time from an empty state: ``q``, ``k
    (T, H, d_k)``, ``v (T, H, d_v)``, ``g``, ``beta (T, H)`` to ``(o (T, H,
    d_v), the state after the last token (H, d_k, d_v))``."""
    with jax.default_matmul_precision("highest"):
        def step(s, row):
            q_t, k_t, v_t, g_t, b_t = row
            s = jnp.exp(g_t)[:, None, None] * s
            d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = s + k_t[:, :, None] * d[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        s0 = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
        s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
        return o, s


@partial(jax.jit, static_argnames=("eps", "k_heads", "v_heads", "d_k", "d_v"))
def gdn_mixer(h, p, *, eps, k_heads, v_heads, d_k, d_v):
    """The Gated DeltaNet mixer over the whole sequence ``h (T, d)``
    (already normed), from an empty state: ``(output (T, d), the state
    after the last token (Hv, d_k, d_v))``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t, rep = h.shape[0], v_heads // k_heads
        nk, nv = k_heads * d_k, v_heads * d_v
        qkvz = h @ p["in_qkvz"].astype(f32)        # served: [q | k | v | z]
        ba = h @ p["in_ba"].astype(f32)            # served: [b | a]
        x, z = qkvz[:, :2 * nk + nv], qkvz[:, 2 * nk + nv:].reshape(
            t, v_heads, d_v)
        b, a = ba[:, :v_heads], ba[:, v_heads:]
        w = p["conv_w"].astype(f32)                        # (taps, channels)
        taps = w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, x.shape[1]), f32), x], 0)
        x = jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(taps)))
        q = x[:, :nk].reshape(t, k_heads, d_k)
        k = x[:, nk:2 * nk].reshape(t, k_heads, d_k)
        v = x[:, 2 * nk:].reshape(t, v_heads, d_v)
        l2 = lambda y: y * jax.lax.rsqrt(          # noqa: E731
            jnp.square(y).sum(-1, keepdims=True) + 1e-6)
        q, k = l2(q) * d_k ** -0.5, l2(k)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            a + p["dt_bias"].astype(f32))
        o, s = delta_rule(jnp.repeat(q, rep, axis=1),
                          jnp.repeat(k, rep, axis=1), v, g, beta)
        o = _rmsnorm(o, p["norm"]["scale"], eps) * jax.nn.silu(z)
        return o.reshape(t, -1) @ p["out_proj"].astype(f32), s


@partial(jax.jit, static_argnames=("eps", "theta", "rot", "n_heads",
                                   "n_kv_heads", "head_dim", "block"))
def attention_mixer(h, p, *, eps, theta, rot, n_heads, n_kv_heads, head_dim,
                    block):
    """Gated full causal attention over ``h (T, d)`` (already normed), in
    blocks of query positions: ``(output (T, d), keys (T, Hkv, D) after
    their norm and RoPE, values (T, Hkv, D))``, the rows a K/V store
    holds."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t = h.shape[0]
        pos = jnp.arange(t)
        qkv = h @ p["wqkv"].astype(f32)
        nq, nk = 2 * n_heads * head_dim, n_kv_heads * head_dim
        qg = qkv[:, :nq].reshape(t, n_heads, 2 * head_dim)
        q, gate = qg[..., :head_dim], qg[..., head_dim:]
        k = qkv[:, nq:nq + nk].reshape(t, n_kv_heads, head_dim)
        v = qkv[:, nq + nk:].reshape(t, n_kv_heads, head_dim)
        q = _rope(_rmsnorm(q, p["q_norm"]["scale"], eps), pos, theta, rot)
        k = _rope(_rmsnorm(k, p["k_norm"]["scale"], eps), pos, theta, rot)
        group = n_heads // n_kv_heads
        kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = (jnp.einsum("qhd,khd->hqk", q[s:e], kk[:e])
                      / np.sqrt(head_dim))
            mask = pos[s:e, None] >= pos[None, :e]
            probs = jax.nn.softmax(
                jnp.where(mask[None], scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", probs, vv[:e]))
        o = jnp.concatenate(outs, 0) * jax.nn.sigmoid(gate)
        return o.reshape(t, -1) @ p["wo"].astype(f32), k, v


@jax.jit
def _swiglu(h, gate, up, down):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        return (jax.nn.silu(h @ gate.astype(f32)) * (h @ up.astype(f32))) \
            @ down.astype(f32)


@partial(jax.jit, static_argnames=("eps", "top_k"))
def _route(x, ln2, router, *, eps, top_k):
    """``(norm(x), chosen (T, k), weights (T, k))`` over ALL the router's
    columns."""
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


@jax.jit
def _shared(h, p):
    with jax.default_matmul_precision("highest"):
        return _swiglu(h, p["w1"], p["w3"], p["w2"]) * jax.nn.sigmoid(
            h @ p["gate"].astype(jnp.float32))


def moe(x, p, *, eps, top_k, first, shared=True):
    """``moe(norm(x))``: a loop over the experts held here (``p["moe"]
    ["w13"]`` holds experts ``first ..``), plus the gated shared expert."""
    m = p["moe"]
    h, chosen, w = _route(x, p["ln2"]["scale"], m["router"], eps=eps,
                          top_k=top_k)
    out = _shared(h, p["shared"]) if shared else jnp.zeros_like(x)
    chosen, w = np.asarray(chosen), np.asarray(w)
    for e in range(m["w13"].shape[0]):           # one held expert at a time
        rows, slot = np.nonzero(chosen == first + e)
        if rows.size == 0:
            continue
        # padded to a power of two with weight 0 (on row 0), so that the
        # jitted product compiles for a handful of sizes, not for every one
        n = max(8, 1 << int(rows.size - 1).bit_length())
        idx, wts = np.zeros(n, np.int32), np.zeros(n, np.float32)
        idx[:rows.size], wts[:rows.size] = rows, w[rows, slot]
        out = _add_expert(out, h, idx, wts, m["w13"][e], m["w2"][e])
    return out


@partial(jax.jit, static_argnames=("eps",))
def _head(x_last, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale, eps) @ lm_head.astype(jnp.float32)


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys and of the
    share the configuration states (``share``: the first expert held)."""
    head_dim = int(config["head_dim"])
    return dict(n_layers=int(config["num_hidden_layers"]),
                rms_norm_eps=float(config["rms_norm_eps"]),
                rope_theta=float(config["rope_theta"]),
                rot=int(head_dim * float(config["partial_rotary_factor"])),
                n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                head_dim=head_dim,
                period=int(config["full_attention_interval"]),
                k_heads=int(config["linear_num_key_heads"]),
                v_heads=int(config["linear_num_value_heads"]),
                d_k=int(config["linear_key_head_dim"]),
                d_v=int(config["linear_value_head_dim"]),
                top_k=int(config["num_experts_per_tok"]),
                first=int(config.get("share", {}).get("first_expert", 0)))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, rms_norm_eps: float, rope_theta: float,
                rot: int, n_heads: int, n_kv_heads: int, head_dim: int,
                period: int, k_heads: int, v_heads: int, d_k: int, d_v: int,
                top_k: int, first: int, block: int = 256,
                stores: bool = False):
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``; with ``stores`` also what a server
    would hold after it: ``(logits, {"state": the Gated DeltaNet layers'
    states after the last token (layers, Hv, d_k, d_v), "kv": the attention
    layers' key and value rows (layers, 2, T, Hkv * D)})``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    eps = rms_norm_eps
    state, kv = [], []
    for i in range(n_layers):
        p = params[f"layer{i}"]
        if ("gdn" in p) == ((i + 1) % period == 0):
            raise ValueError(f"layer {i}: the weights and the published "
                             "layer order disagree on its mixer")
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        if "gdn" in p:
            mixed, s = gdn_mixer(h, p["gdn"], eps=eps, k_heads=k_heads,
                                 v_heads=v_heads, d_k=d_k, d_v=d_v)
            if stores:
                state.append(np.asarray(s))
        else:
            mixed, k, v = attention_mixer(
                h, {k: p[k] for k in ("wqkv", "q_norm", "k_norm", "wo")},
                eps=eps, theta=rope_theta, rot=rot, n_heads=n_heads,
                n_kv_heads=n_kv_heads, head_dim=head_dim, block=block)
            if stores:
                kv.append(np.asarray(jnp.stack([k, v]).reshape(
                    2, len(tokens), -1)))
        x = x + mixed
        x = x + moe(x, p, eps=eps, top_k=top_k, first=first)
    logits = np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                              params["lm_head"], eps=eps), np.float32)
    if stores:
        return logits, {"state": np.stack(state), "kv": np.stack(kv)}
    return logits


def store_errors(state, kv, want: Dict[str, np.ndarray]) -> Dict[str, float]:
    """What the server holds after a stream against what the reference
    would hold (``last_logits(..., stores=True)``): ``state (Hv, d_k, d_v)``
    is the FIRST Gated DeltaNet layer's state of the stream's lane, ``kv (2,
    T, Hkv * D)`` the FIRST attention layer's key and value rows of the
    stream's pages.  ``state_err``: the Frobenius norm of the difference
    over the reference's; ``kv_err``: the larger of the keys' and the
    values' MEDIAN over the rows of a row's difference over the row's norm
    (a token whose experts flipped upstream is one row)."""
    ref = want["state"][0].astype(np.float64)
    state_err = np.linalg.norm(state - ref) / np.linalg.norm(ref)
    rows = want["kv"][0].astype(np.float64)
    if kv.shape != rows.shape or state.shape != ref.shape:
        raise ValueError(f"served stores {state.shape}, {kv.shape} against "
                         f"the reference's {ref.shape}, {rows.shape}")
    off = (np.linalg.norm(kv - rows, axis=-1)
           / np.linalg.norm(rows, axis=-1))
    return {"state_err": float(state_err),
            "kv_err": float(np.median(off, axis=-1).max())}


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 stores=None, **hyper) -> Dict[str, Any]:
    """One served greedy stream against the reference: one forward over
    ``prompt + tokens[:-1]``, whose last ``len(tokens)`` logit rows predict
    ``tokens``.  Per token: ``logprob_err``, the served log-probability
    against the reference's, and ``argmax_gap``, the reference's largest
    logit minus its logit of the emitted token.  With ``stores`` (``(state,
    kv)`` the server held once the stream had ended: every token of that
    forward taken in, and nothing else) also :func:`store_errors` of them,
    from the same forward."""
    n = len(tokens)
    fed = list(prompt) + list(tokens[:-1])
    logits = last_logits(params, fed, n, stores=stores is not None, **hyper)
    out: Dict[str, Any] = {}
    if stores is not None:
        logits, want = logits
        out = store_errors(*stores, want)
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
    together.  ``logprob_err`` and ``argmax_gap`` are the LOWER QUARTILES
    over all the tokens (what TOLERANCE judges); the median, the largest
    and ``flipped_share`` (tokens whose error is past 0.05, as a flipped
    expert makes it) are reported beside them and judge nothing.  Where the
    streams carry them, ``state_err`` and ``kv_err`` are the MEDIANS over
    the streams (what STATE_TOLERANCE and KV_TOLERANCE judge)."""
    err = np.concatenate([s["logprob_err"] for s in streams])
    gap = np.concatenate([s["argmax_gap"] for s in streams])
    out = {
        "logprob_err": float(np.quantile(err, QUANTILE)),
        "argmax_gap": float(np.quantile(gap, QUANTILE)),
        "logprob_err_median": float(np.median(err)),
        "logprob_err_max": float(err.max()),
        "flipped_share": float((err > 0.05).mean()),
    }
    for name in ("state_err", "kv_err"):
        if all(name in s for s in streams):
            out[name] = float(np.median([s[name] for s in streams]))
    return out


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """:func:`summary` of one stream alone."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
