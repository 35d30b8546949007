"""Plain reference: ZAYA1-8B (``model_type`` ``zaya``).

Written from the published ``config.json``, the two papers that describe the
model (Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1
technical report, arXiv:2511.17127) and, for what neither settles, the
configuration file's ``assumed``; straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: ONE causal forward over
the whole sequence, the convolutions as shifts of the whole sequence, the
attention in blocks of query rows, the experts in a loop; no kernel, no
cache, no lane state, no chunk, no grouped product, nothing imported from
the program.

A layer over the residual ``x (T, C)`` (all 40 published layers alike), with
``H`` query heads on ``G`` KV heads of ``D`` (8 on 2 of 128)::

    h      = RMSNorm(x)
    c      = h [W_q | W_k]                       (T, (H + G) D), q heads first
    a_t    = b0 + sum_j w0[j] * c_(t - (k0 - 1 - j))         depthwise, k0 taps
    d_t    = b1 + sum_j a_(t - (k1 - 1 - j)) W1[j]          W1[j]: H + G blocks
                                                            of D x D, k1 taps
             (both start from zeros: c_(-1) = a_(-1) = 0)
    q      = d[:, :H D] + (q~_i + k~_g) / 2                  g = i // (H / G)
    k      = d[:, H D:] + (mean_i q~_i + k~_g) / 2           i over g's heads
    q, k   = sqrt(D) q / |q|_2,  tau_g sqrt(D) k / |k|_2     a head at a time
    RoPE (rotate-half) over the first ``rotary`` columns of each head
    v_t    = [h_t W_v1 ; h_(t-1) W_v2]           cut into the G heads in order
    o      = causal softmax(q k^T D^-0.5) v      GQA
    x      = s_r (x + b_r) + s_o (o W_o + b_o)   residual scaling

    h      = RMSNorm(x)
    r      = h W_d + b_d;  r += gamma * r_prev   (layer 0 has no gamma)
    u      = RMSNorm(r);  z = W_3 gelu(W_2 gelu(W_1 u + b_1) + b_2)
    p      = softmax(z);  e = argmax(p + beta)   (ties to the lower id)
    y      = p_e SwiGLU_e(h)  for an FFN expert, p_e h for the skip column
    x      = s_r (x + b_r) + s_o (y + b_o);  r_prev = r

then ``logits = RMSNorm(x) E^T`` with the tied table ``E``.  GELU is the
exact one (erf).

It is handed the weights in the program's tree (bf16; ``tpulab/models/
spec.py`` documents it: ``cca.in_proj`` = ``[W_q | W_k | W_v1 | W_v2]``, an
expert's ``w13[e]`` = ``[gate | up]``).  Departures, shared with the program
and stated in the configuration file: RoPE in the rotate-half convention (a
column permutation of an interleaved storage under seeded weights); random
weights emit no EOS.  The model has no prediction layer.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream, and independent streams
#: (prompts drawn apart) a prompt length, their tokens judged TOGETHER: kind
#: ``keye_vl2``'s construction and reasons (top-1 routing is discontinuous:
#: where the two largest of ``p + beta`` lie closer than the served path's
#: bf16 rounding moves them, the served path runs another expert than the
#: float32 reference and that token's logits move by several times what
#: rounding alone moves them; a loss of precision moves EVERY token, the best
#: quarter of them too).
REFERENCE_STEPS = 32
REFERENCE_STREAMS = 4
QUANTILE = 0.25

#: Limits, each with its readings on the v5e at the published widths through
#: the Generate RPC under the cell's engine (TOLERANCE_READINGS, each "after
#: the 24-token prompts / after the 5,000-token prompts", four streams a
#: length: my chip runs, PR 54; PERF.md section 6).  Eight numbers of every
#: prompt length, each under a limit; the next precision below
#: the configuration's, and the fault this model adds, each fail by at least
#: one of them:
#:
#: ``logprob_err`` / ``argmax_gap``: the LOWER QUARTILE over the emitted
#: tokens of a length's streams of the served log-probability against the
#: reference's (and of how far the emitted token's reference logit lies
#: under the reference's largest), a limit a length (:func:`tolerance`).
#: Short prompts: the geometric middle of the largest of sixteen bf16 seeds
#: (0.0195; fifteen read 0.0068-0.0128) and the smallest reading with the
#: K/V pages in fp8 (e4m3): 1.7 x the one, 0.58 of the other.  Long prompts: 2 x
#: the largest bf16 reading, and there it does NOT part fp8 pages (one of
#: two seeds reads under it: behind 5,000 keys a rounded row is one of
#: thousands): ``kv_err`` does.
#:
#: ``kv_err``: the larger of the keys' and the values' MEDIAN over the rows
#: of layer 0's K/V rows in the stream's pages, a row's difference over the
#: row's norm: a store kept one precision lower moves every row (bf16
#: 0.0032-0.0033 on every stream of every seed, fp8 pages 8 x that).
#:
#: ``kv_row_max``: the LARGEST such row error.  The fault this model adds: a
#: program that starts every chunk of a prompt from zero tails writes ONE
#: wrong key and ONE half-wrong value a chunk boundary a layer (nine rows of
#: 5,031), which the logits of 32 tokens behind 5,000 keys do not show (its
#: readings lie inside bf16's band) and a median over the rows does not
#: either; the largest row reads 0.85-0.94 where bf16 reads 0.004.  Tails
#: kept in fp8 show here too (0.022), on both lengths.
#:
#: ``state_err``: layer 0's three tails in the stream's lane once it has
#: ended (the last token's ``c`` and ``a`` rows and its ``h W_v2``), the
#: norm of the difference over the reference's: tails kept narrower (fp8:
#: 0.023 where bf16 reads 0.0025-0.0027), a slot another request's, or a
#: tail that did not follow the last token.
#:
#: These three read layer 0 because no router reaches it.  From layer 1 on
#: a row also moves wherever the served path chose another expert for its
#: token in a layer before (13-43 % of a stream's tokens lie past 0.05 on
#: the logits), and it moves as far as a lost tail moves it: the largest
#: row of layers 1-15 reads 0.30-1.09 under plain bf16 serving and 0.88-1.5
#: with every chunk started from zero tails, so no limit parts them.  What
#: a flip leaves standing is judged on EVERY layer, one limit
#: (``LAYERS_TOLERANCE``) for three numbers (:func:`summary`), each the
#: worst of the sixteen layers: a flip moves single tokens of single
#: streams, a tail or a row lost, zero or another layer's moves the same
#: rows of every stream to 0.8-1.4.
#:
#: ``layers_kv_err``: the median over the streams of a layer's MEDIAN row
#: (bf16 0.024-0.109 / 0.015-0.045 over nine seeds; every layer past 0 on
#: lane-state slot 1: 1.17 on the short prompts; a layer's rows in another
#: layer's pages would read 1.4).  ``layers_state_err``: the SMALLEST over
#: the streams of a layer's tails (bf16 0.0097-0.062 / 0.0090-0.014, a
#: flipped stream alone up to 0.22; that fault 1.39: a slot never written
#: reads 1.0).  ``layers_seam_err``: the median over all the streams' rows
#: at which a chunk of the prompt began (nine a stream of 5,000; none at 24)
#: (bf16 0.013-0.051; zero tails at every chunk start 1.35, in layers 1-15
#: alone 0.90, where layer 0's ``kv_row_max`` sees nothing; fp8 pages 0.10).
#: The limit is 3.7 x bf16's largest reading and 0.44 of the smallest
#: fault's.
TOLERANCE = 0.016
TOLERANCE_SHORT = 0.033
#: prompts under this many tokens are judged by TOLERANCE_SHORT
SHORT_PROMPT = 256
KV_TOLERANCE = 0.009
KV_ROW_TOLERANCE = 0.01
STATE_TOLERANCE = 0.008
#: ``layers_kv_err``, ``layers_state_err``, ``layers_seam_err`` (below)
LAYERS_TOLERANCE = 0.4
TOLERANCE_READINGS: Dict[str, str] = {
    "bf16": "logprob_err 0.0068-0.0195 / 0.0029-0.0080; kv_err 0.0031-0.0033 "
            "/ 0.0032; kv_row_max 0.0036-0.0039 / 0.0040-0.0042; state_err "
            "0.0025-0.0027 / 0.0025-0.0027 (sixteen seeds)",
    "fp8_pages": "logprob_err 0.0569-0.0629 / 0.0138-0.0243; kv_err "
                 "0.0268-0.0269 / 0.0266-0.0267; kv_row_max 0.0303-0.0305 / "
                 "0.0323; state_err as bf16 (two seeds)",
    "zero_tails": "logprob_err as bf16 (one chunk) / 0.0063-0.0104; kv_err "
                  "0.0032 / 0.0032; kv_row_max as bf16 / 0.915-0.922 (a "
                  "stream alone 0.849-0.944); state_err as bf16 (two seeds)",
    "fp8_tails": "logprob_err 0.0255 / 0.0055; kv_err 0.0180 / 0.0032; "
                 "kv_row_max 0.0220 / 0.0218; state_err 0.0236 / 0.0234 "
                 "(one seed)",
    "every layer, bf16": "layers_kv_err 0.024-0.109 / 0.015-0.045; "
                         "layers_state_err 0.0097-0.062 / 0.0090-0.014; "
                         "layers_seam_err - / 0.013-0.051 (nine seeds); the "
                         "largest row of layers 1-15, which judges nothing: "
                         "0.30-0.41 / 1.04-1.09 (two seeds)",
    "every layer, zero_tails": "layers_seam_err - / 1.35; layers_kv_err "
                               "0.064 / 0.082; layers_state_err 0.062 / "
                               "0.021 (one seed)",
    "every layer, zero_tails in layers 1-15 alone": "layers_seam_err - / "
        "0.90; kv_row_max (layer 0) as bf16; layers_kv_err, "
        "layers_state_err as bf16 (one seed)",
    "every layer, layers 1-15 on lane-state slot 1": "layers_state_err 1.40 "
        "/ 1.39; layers_kv_err 1.17 / 0.042; layers_seam_err - / 1.17; "
        "logprob_err 0.77 / 0.053 (one seed)",
    "every layer, fp8_pages": "layers_kv_err 0.22 / 0.11; layers_state_err "
                              "0.092 / 0.035; layers_seam_err - / 0.10 "
                              "(one seed; kv_err fails it)",
}


def tolerance(prompt_len: int) -> float:
    """The limit on the lower quartiles of a prompt length's streams."""
    return TOLERANCE_SHORT if prompt_len < SHORT_PROMPT else TOLERANCE


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _shift(x, n: int):
    """``x (T, ...)`` moved ``n`` tokens later, zeros before the start."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], axis=0)


def _rope(x, positions, theta: float, rotary: int):
    """x (T, H, D): rotate-half over the first ``rotary`` columns."""
    half = rotary // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    r = x[..., :rotary]
    turned = r * cos + jnp.concatenate([-r[..., half:], r[..., :half]],
                                       -1) * sin
    return jnp.concatenate([turned, x[..., rotary:]], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "eps"))
def cca_qkv(x, ln, p, *, n_heads, n_kv_heads, eps):
    """Steps 1-4 and 6 over the whole sequence ``x (T, C)``: ``(q (T, H,
    D), k (T, G, D)`` normed and before RoPE, ``v (T, G, D)``, and the three
    rows a lane would keep behind the LAST token: ``c_T``, ``a_T``, ``h_T
    W_v2``)``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        hq, g = n_heads, n_kv_heads
        w0, w1 = p["conv0_w"].astype(f32), p["conv1_w"].astype(f32)
        d = w1.shape[-1]
        t = x.shape[0]
        h = _rmsnorm(x, ln, eps)
        proj = h @ p["in_proj"].astype(f32)
        nq, nc = hq * d, (hq + g) * d
        c, v1, v2 = (proj[:, :nc], proj[:, nc:nc + g * d // 2],
                     proj[:, nc + g * d // 2:])
        k0, k1 = w0.shape[0], w1.shape[0]
        a = p["conv0_b"].astype(f32) + sum(
            w0[j] * _shift(c, k0 - 1 - j) for j in range(k0))
        conv = p["conv1_b"].astype(f32).reshape(hq + g, d) + sum(
            jnp.einsum("tgd,gde->tge",
                       _shift(a, k1 - 1 - j).reshape(t, hq + g, d), w1[j])
            for j in range(k1))
        rep = hq // g
        qt = c[:, :nq].reshape(t, g, rep, d)
        kt = c[:, nq:].reshape(t, g, 1, d)
        q = conv[:, :hq].reshape(t, g, rep, d) + (qt + kt) / 2
        k = conv[:, hq:].reshape(t, g, 1, d) + (
            qt.mean(axis=2, keepdims=True) + kt) / 2
        unit = lambda z: z / jnp.maximum(
            jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-12) * d ** 0.5
        q = unit(q).reshape(t, hq, d)
        k = unit(k).reshape(t, g, d)
        if "tau" in p:
            k = k * p["tau"].astype(f32)[None, :, None]
        v = jnp.concatenate([v1, _shift(v2, 1)], -1).reshape(t, g, d)
        return q, k, v, (c[-1], a[-1], v2[-1])


@partial(jax.jit, static_argnames=("theta", "rotary", "block"))
def attend(q, k, v, wo, *, theta, rotary, block):
    """RoPE, causal GQA softmax attention in blocks of query rows, ``W_o``:
    ``(out (T, C), the roped keys (T, G, D))``."""
    with jax.default_matmul_precision("highest"):
        t, hq, d = q.shape
        g = k.shape[1]
        pos = jnp.arange(t)
        if theta:
            q, k = _rope(q, pos, theta, rotary), _rope(k, pos, theta, rotary)
        qg = q.reshape(t, g, hq // g, d)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = jnp.einsum("qgrd,kgd->grqk", qg[s:e], k[:e]) * d ** -0.5
            mask = pos[s:e, None] >= pos[None, :e]
            probs = jax.nn.softmax(jnp.where(mask[None, None], scores,
                                             -jnp.inf), axis=-1)
            outs.append(jnp.einsum("grqk,kgd->qgrd", probs, v[:e])
                        .reshape(e - s, -1))
        return jnp.concatenate(outs, 0) @ wo.astype(jnp.float32), k


@jax.jit
def residual(x, y, r):
    """``s_r (x + b_r) + s_o (y + b_o)``."""
    f32 = jnp.float32
    return (r["s_r"].astype(f32) * (x + r["b_r"].astype(f32))
            + r["s_o"].astype(f32) * (y + r["b_o"].astype(f32)))


@partial(jax.jit, static_argnames=("eps",))
def router(h, r, bias, prev, *, eps):
    """``(chosen (T,), weight (T,), state (T, W))`` of the normed rows ``h``:
    the MLP router with depth averaging (``prev`` None on layer 0)."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        state = h @ r["down"].astype(f32) + r["down_b"].astype(f32)
        if prev is not None and "gamma" in r:
            state = state + r["gamma"].astype(f32) * prev
        u = _rmsnorm(state, r["norm"]["scale"], eps)
        for w, b in (("w1", "b1"), ("w2", "b2")):
            u = jax.nn.gelu(u @ r[w].astype(f32) + r[b].astype(f32),
                            approximate=False)
        p = jax.nn.softmax(u @ r["w3"].astype(f32), axis=-1)
        # the largest of p + beta; argmax takes the lower id of a tie
        chosen = jnp.argmax(p + bias.astype(f32), axis=-1)
        return chosen, jnp.take_along_axis(p, chosen[:, None], 1)[:, 0], state


@jax.jit
def _matmul(h, w):
    """One matrix, upcast alone."""
    with jax.default_matmul_precision("highest"):
        return h @ w.astype(jnp.float32)


@jax.jit
def _add_expert(out, h, idx, wts, w13, w2):
    """``out[idx] += wts * SwiGLU_e(h[idx])``; ``w13`` is ``[gate | up]``."""
    f = w2.shape[0]
    rows = h[idx]
    y = _matmul(jax.nn.silu(_matmul(rows, w13[:, :f]))
                * _matmul(rows, w13[:, f:]), w2)
    return out.at[idx].add(y * wts[:, None])


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, *, eps):
    return _rmsnorm(x, scale, eps)


def experts(x, p, prev, *, eps):
    """The expert sublayer's ``(y (T, C), router state)``, no residual: the
    experts one at a time, each upcast alone and applied to the rows that
    chose it; the LAST column of the router is the skip column."""
    h = _norm(x, p["ln2"]["scale"], eps=eps)
    m = p["moe"]
    chosen, w, state = router(h, m["router"], m["bias"], prev, eps=eps)
    chosen, w = np.asarray(chosen), np.asarray(w)
    n_ffn = m["w2"].shape[0]
    out = h * jnp.asarray(np.where(chosen >= n_ffn, w, 0.0))[:, None]
    for e in range(n_ffn):
        rows = np.nonzero(chosen == e)[0]
        if rows.size == 0:
            continue
        # padded to a power of two with weight 0 (on row 0), so that the
        # jitted product compiles for a handful of sizes, not for every one
        n = max(8, 1 << int(rows.size - 1).bit_length())
        idx, wts = np.zeros(n, np.int32), np.zeros(n, np.float32)
        idx[:rows.size], wts[:rows.size] = rows, w[rows]
        out = _add_expert(out, h, idx, wts, m["w13"][e], m["w2"][e])
    return out, state


@partial(jax.jit, static_argnames=("eps",))
def _head(x_last, scale, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale, eps) @ embed.astype(jnp.float32).T


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys."""
    rope = (config.get("rope_parameters") or {}).get("hybrid") or config
    head_dim = int(config["head_dim"])
    return dict(
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        rotary=int(head_dim * float(rope.get("partial_rotary_factor", 1))))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, n_heads: int, n_kv_heads: int,
                rms_norm_eps: float, rope_theta: float, rotary: int,
                block: int = 256, stores: bool = False):
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``.  With ``stores`` also what a server
    holds of EVERY layer once it has taken in every token: ``(logits, {"kv"
    (L, 2, T, G * D)`` the roped keys and the values, ``"state" (L, S)`` the
    last token's ``[c ; a ; h W_v2]`` in one row a layer``})``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    prev, rows, tails = None, [], []
    for i in range(n_layers):
        p = params[f"layer{i}"]
        q, k, v, kept = cca_qkv(x, p["ln1"]["scale"], p["cca"],
                                n_heads=n_heads, n_kv_heads=n_kv_heads,
                                eps=rms_norm_eps)
        y, k = attend(q, k, v, p["wo"], theta=rope_theta, rotary=rotary,
                      block=block)
        if stores:
            t = len(tokens)
            rows.append(np.stack([np.asarray(k).reshape(t, -1),
                                  np.asarray(v).reshape(t, -1)]))
            tails.append(np.concatenate([np.asarray(z) for z in kept]))
        x = residual(x, y, p["res_attn"])
        y, prev = experts(x, p, prev, eps=rms_norm_eps)
        x = residual(x, y, p["res_ffn"])
    logits = np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                              params["embed"], eps=rms_norm_eps), np.float32)
    if stores:
        return logits, {"kv": np.stack(rows), "state": np.stack(tails)}
    return logits


def store_errors(state, kv, want: Dict[str, np.ndarray],
                 seams: Sequence[int] = ()) -> Dict[str, Any]:
    """What the server holds after a stream against what the reference would
    (``last_logits(..., stores=True)``): ``state (L, S)`` the three tails of
    every layer in the stream's lane as one row a layer, ``kv (L, 2, T, G *
    D)`` every layer's key and value rows in the stream's pages, ``seams``
    the rows at which a chunk of the prompt began (a dispatch that took its
    window from the lane's tails).

    A row's error is its difference over the row's norm, the larger of the
    key's and the value's.  Of LAYER 0, which no router reaches:
    ``state_err`` the tails', ``kv_err`` the MEDIAN row's, ``kv_row_max``
    the largest row's.  Of every layer (:func:`summary` judges them; a
    deeper row also moves, as far as a lost tail moves it, wherever the
    served path chose another expert for its token in a layer before):
    ``layer_state_err (L,)``, ``layer_kv_err (L,)`` the median row's and
    ``seam_rows (L, len(seams))`` the rows' at the seams."""
    ref = want["state"].astype(np.float64)
    rows = want["kv"].astype(np.float64)
    if kv.shape != rows.shape or state.shape != ref.shape:
        raise ValueError(f"served stores {state.shape}, {kv.shape} against "
                         f"the reference's {ref.shape}, {rows.shape}")
    off = (np.linalg.norm(kv - rows, axis=-1)
           / np.linalg.norm(rows, axis=-1))                 # (L, 2, T)
    tails = (np.linalg.norm(state - ref, axis=-1)
             / np.linalg.norm(ref, axis=-1))                # (L,)
    return {"state_err": float(tails[0]),
            "kv_err": float(np.median(off[0], axis=-1).max()),
            "kv_row_max": float(off[0].max()),
            "layer_state_err": tails,
            "layer_kv_err": np.median(off, axis=-1).max(-1),
            "seam_rows": off[:, :, list(seams)].max(1)}


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 stores=None, seams: Sequence[int] = (),
                 **hyper) -> Dict[str, Any]:
    """A served greedy stream against ONE forward over ``prompt +
    tokens[:-1]``, whose last ``len(tokens)`` logit rows predict ``tokens``:
    per token, ``logprob_err`` (the served log-probability against the
    reference's) and ``argmax_gap`` (the reference's largest logit minus its
    logit of the emitted token).  With ``stores`` (``(state, kv)`` the server
    held once the stream had ended: every token of that forward taken in,
    and nothing else; ``seams`` where the prompt's chunks began) also
    :func:`store_errors` of them, from the same forward."""
    n = len(tokens)
    fed = list(prompt) + list(tokens[:-1])
    logits = last_logits(params, fed, n, stores=stores is not None, **hyper)
    out: Dict[str, Any] = {}
    if stores is not None:
        logits, want = logits
        out = store_errors(*stores, want, seams)
    logits = logits.astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows, toks = np.arange(n), np.asarray(tokens)
    return dict(out,
                logprob_err=np.abs(logp[rows, toks] - np.asarray(logprobs)),
                argmax_gap=logits.max(-1) - logits[rows, toks])


def summary(streams: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """The streams of one prompt length judged together: ``logprob_err`` and
    ``argmax_gap`` are the LOWER QUARTILES over all their tokens; the median,
    the largest and the share of tokens past 0.05 (as a flipped expert makes
    it) judge nothing.  Where the streams carry them, ``state_err``,
    ``kv_err`` and ``kv_row_max`` (layer 0's) are the MEDIANS over the
    streams, and every layer is judged by what a flipped expert leaves
    standing (it moves single tokens of single streams, a fault every
    stream): ``layers_kv_err`` the worst layer's median over the streams of
    its median row, ``layers_state_err`` the worst layer's SMALLEST over the
    streams of its tails' error (a stream's tails are its last token's),
    ``layers_seam_err`` the worst layer's median over all the streams' rows
    at a seam (left out where no prompt was cut)."""
    err = np.concatenate([s["logprob_err"] for s in streams])
    gap = np.concatenate([s["argmax_gap"] for s in streams])
    out = {"logprob_err": float(np.quantile(err, QUANTILE)),
           "argmax_gap": float(np.quantile(gap, QUANTILE)),
           "logprob_err_median": float(np.median(err)),
           "logprob_err_max": float(err.max()),
           "flipped_share": float((err > 0.05).mean())}
    for name in ("state_err", "kv_err", "kv_row_max"):
        if all(name in s for s in streams):
            out[name] = float(np.median([s[name] for s in streams]))
    if all("seam_rows" in s for s in streams):
        stack = lambda name: np.stack([s[name] for s in streams])
        out["layers_kv_err"] = float(
            np.median(stack("layer_kv_err"), axis=0).max())
        out["layers_state_err"] = float(
            stack("layer_state_err").min(0).max())
        seams = np.concatenate([s["seam_rows"] for s in streams], axis=1)
        if seams.shape[1]:
            out["layers_seam_err"] = float(np.median(seams, axis=1).max())
    return out


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """One stream alone (the tests' form)."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
