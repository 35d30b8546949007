"""Plain reference: NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type``
``nemotron_h``).

Written from the published ``config.json`` keys and the layers the model type
names (``NemotronHBlock``, ``NemotronHMamba2Mixer``, ``NemotronHAttention``,
``NemotronHMOE`` / ``NemotronHTopkRouter``, ``NemotronHMLP`` of the published
modelling code, as remembered: there is no network here, so what the keys do
not settle is listed under ``assumed`` in the configuration file);
straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no batching,
no chunk form, no grouped product, nothing imported from the program.

``N(.)`` is RMSNorm at ``layer_norm_epsilon``.  Layer ``i`` is ONE sublayer,
by letter ``i`` of ``hybrid_override_pattern``::

    x <- x + f_i(N(x; the layer's one norm))      f_i: M, * or E

*M, Mamba-2* (``H`` heads of ``P`` channels, ``G`` groups, state width
``N``; ``d_inner = H P``): ``[z | xBC | dt] = h W_in``; ``xBC_t <-
silu(sum_d w_d xBC_{t-3+d} + b)`` (depthwise, causal, ``conv_kernel`` taps
over the ``d_inner + 2 G N`` channels of ``[x | B | C]`` together, zeros
before the sequence); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a
head; head ``j`` uses group ``j // (H / G)``; per head, ``S_{-1} = 0``::

    S <- exp(dt_t A) S + (dt_t x_t) (x) B_t;    y_t = S C_t + D x_t

``y <- N_group(y * silu(z))`` (the gate BEFORE the norm, the norm over each
group's ``d_inner / G`` channels, one weight of ``d_inner``); ``f = y
W_out``.  Here: ONE sequential ``lax.scan`` over the tokens: the recurrence
is the definition.

*\\*, attention* (``Hq`` query heads on ``Hkv`` KV heads of ``head_dim``): no
positional rotation; full causal softmax at ``head_dim^-0.5``; ``f = concat_j
o_j W_o``.

*E, experts*: ``s = sigmoid(h W_r)`` over ALL ``E`` columns in float32; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` are chosen
(ties to the lower id), weighted ``s / sum over the chosen`` (``+ 1e-20``)
times ``routed_scaling_factor``; ``f = sum over the chosen experts HELD
HERE`` of ``w_e W2_e relu(W1_e h)^2``, a loop over the held experts (``first
.. first + held``: what the absent ones would add is left out, as the served
program leaves it out), ``+ Ws2 relu(Ws1 h)^2``, the shared expert, on every
row, unscaled.

Final norm, untied head (over the slice of the vocabulary held here).

It is handed the *served* weights (bf16, the program's layout, documented in
``tpulab/models/spec.py``) and upcasts one layer, one expert at a time.  The
served experts carry zero columns of ``w1`` and zero rows of ``w2`` past the
published width (1,856 -> 1,920: whole lanes); a product over them adds
exact zeros, so this file takes the matrices as they come.
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
#: Top-6 of 128 routing is discontinuous and only an eighth of the experts
#: add anything here, so a flip adds or drops a whole expert's output, and a
#: greedy stream on seeded weights settles on one or two ids, so one stream
#: carries one error.
REFERENCE_STEPS = 32
REFERENCE_STREAMS = 4
QUANTILE = 0.25

#: Largest LOWER QUARTILE, over the emitted tokens of the streams of one
#: prompt length, of the difference between the served path and this
#: reference, in logit units, on (a) the log-probability of each emitted
#: token and (b) how far the emitted token's reference logit lies under the
#: reference's largest.  Its size from TOLERANCE_READINGS (my chip runs, PR
#: 60: the v5e, the published widths, all 52 layers, through the Generate
#: RPC; the lower quartile of ``logprob_err`` after the 24-token prompts /
#: after the 2,000-token prompts: three rounds of 512 and one of 464, fifteen
#: chunk boundaries and three round boundaries the state crosses; ``bf16``
#: over the seeds of every run made, the faults on seed 3160000001).  bf16
#: serving reads what ``qwen3next-l8-ep4`` reads (0.008-0.018) and for its
#: reason: a recurrence's output is LINEAR in what it carries (no softmax
#: flattens the activations' bf16 rounding) and a norm brings it back to
#: full scale, here through 23 such layers, and ~40 % of the tokens sit past
#: 0.05 (a flipped expert among 6 of 128, of which an eighth add anything):
#: hence the lower quartile.  The limit lies 1.32 x above the largest bf16
#: reading (eleven seeds, two lengths each) and 1.06 x below a router
#: computed in bf16: it guards a GROSS fault.  What the logits CANNOT see is
#: a state kept in bf16 (it reads inside bf16 serving's band: 0.0145 /
#: 0.0186), and the router's fault is caught here by a hair on one seed: so
#: the check also reads what the server HOLDS, each under a limit of its own
#: and with room: STATE_TOLERANCE and ROUTE_TOLERANCE below.
TOLERANCE = 0.031
TOLERANCE_READINGS: Dict[str, str] = {
    "bf16": "0.0135-0.0235 / 0.0109-0.0222",
    "bf16_state": "0.0145 / 0.0186",
    "bf16_router": "0.0328 / 0.0351",
}

#: ``state_err_low``: the FIRST Mamba-2 layer's state of the stream's lane
#: once the stream has ended, against what this reference holds after the
#: same tokens: a HEAD's difference over the head's norm, the LOWER QUARTILE
#: over the 64 heads, the median over the streams of a prompt length.  Layer
#: 0's state is a function of the embeddings alone, so it carries the
#: rounding of ONE projection and ONE convolution and nothing upstream.  The
#: lower quartile over heads, because the heads that remember longest sum
#: the most tokens: the activations' bf16 rounding averages out in them (a
#: head's error falls with the tokens it sums) while a STORE kept in bf16
#: rounds the whole sum again at every dispatch (its error grows with them),
#: so those heads tell the two apart best: over the whole state
#: (``state_err``, reported beside it) bf16 serving reads 0.0035-0.0044 with
#: single streams to 0.0056, and a bf16 store 0.0054-0.0061: 1.37 x, too
#: near; on the lower quartile of the heads 0.0025-0.0029 on every stream
#: against 0.0044-0.0050: 1.7 x.  ``route_err``: the share of the stream's
#: (token, expert) assignments in the FIRST expert layer that the server
#: made differently (its counters against this reference's: half the L1
#: distance of the two histograms over the assignments), the median over the
#: streams; a SHORT stream is 55 tokens x 6 = 330 assignments (one flip is
#: 0.003; bf16 serving's median stream flips 0.5-3 of them over eleven
#: seeds, a stream alone up to 4, a bf16 router's 6), too few to tell a
#: router's precision by, so prompts under SHORT_PROMPT are judged by
#: ROUTE_TOLERANCE_SHORT, ten flips: a gross fault's limit (a selection
#: bias left out; not measured), and the LONG prompts (12,186 assignments a
#: stream: bf16 serving reads 0.0030-0.0037 on every seed) judge the
#: router's precision.  Each limit lies between bf16
#: serving's largest reading and the smallest reading with that store one
#: precision lower, STORE_READINGS (my chip runs, PR 60; the faults on seed
#: 3160000001: the state rounded to bf16 wherever a program leaves it, at 8
#: lanes; the router's logits, scores and weights in bf16): STATE_TOLERANCE
#: 1.24 x above the one (eleven seeds) and 1.29 x below the other,
#: ROUTE_TOLERANCE 1.6 x and 1.7 x.  Neither store's fault moves the other's
#: number.
STATE_TOLERANCE = 0.0036
ROUTE_TOLERANCE = 0.006
ROUTE_TOLERANCE_SHORT = 0.03
#: prompts under this many tokens are judged by ROUTE_TOLERANCE_SHORT
SHORT_PROMPT = 512
STORE_READINGS: Dict[str, Dict[str, str]] = {
    "state_err_low": {"bf16": "0.00281-0.00290 / 0.00260-0.00285",
                      "bf16_state": "0.00472 / 0.00466",
                      "bf16_router": "not read (the whole state: 0.00391 / "
                                     "0.00346, bf16 serving's own)"},
    "state_err": {"bf16": "0.0035-0.0040 / 0.0035-0.0044",
                  "bf16_state": "0.00537 / 0.00606"},
    "route_err": {"bf16": "0.0015-0.0091 / 0.0030-0.0037",
                  "bf16_state": "0.0076 / 0.0034",
                  "bf16_router": "0.0182 / 0.0103"},
}


def route_tolerance(prompt_len: int) -> float:
    return ROUTE_TOLERANCE_SHORT if prompt_len < SHORT_PROMPT \
        else ROUTE_TOLERANCE


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def ssm_scan(x, dt, a, b, c, d):
    """The recurrence, one token at a time from an empty state: ``x (T, H,
    P)``, ``dt (T, H)``, ``a``, ``d (H,)``, ``b``, ``c (T, H, N)`` (a head's
    group's) to ``(y (T, H, P), the state after the last token (H, P,
    N))``."""
    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, (s * c_t[:, None, :]).sum(-1) + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32)
    s, y = jax.lax.scan(step, s0, (x, dt, b, c))
    return y, s


@partial(jax.jit, static_argnames=("eps", "heads", "head_dim", "groups",
                                   "state"))
def mamba2_mixer(h, p, *, eps, heads, head_dim, groups, state):
    """The Mamba-2 mixer over the whole sequence ``h (T, d)`` (already
    normed), from an empty state: ``(output (T, d), the state after the last
    token (H, P, N))``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t, din, gn = h.shape[0], heads * head_dim, groups * state
        zxd = h @ p["in_proj"].astype(f32)         # [z | x | B | C | dt]
        z, xbc, dt = (zxd[:, :din], zxd[:, din:2 * din + 2 * gn],
                      zxd[:, 2 * din + 2 * gn:])
        w = p["conv_w"].astype(f32)                        # (taps, channels)
        taps = w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1]), f32), xbc], 0)
        xbc = jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(taps))
                          + p["conv_b"].astype(f32))
        x = xbc[:, :din].reshape(t, heads, head_dim)
        rep = heads // groups
        b = jnp.repeat(xbc[:, din:din + gn].reshape(t, groups, state), rep, 1)
        c = jnp.repeat(xbc[:, din + gn:].reshape(t, groups, state), rep, 1)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
        y, s = ssm_scan(x, dt, -jnp.exp(p["a_log"].astype(f32)), b, c,
                        p["d"].astype(f32))
        y = (y.reshape(t, din) * jax.nn.silu(z)).reshape(t, groups, -1)
        y = y * jax.lax.rsqrt(jnp.square(y).mean(-1, keepdims=True) + eps)
        y = y.reshape(t, din) * p["norm"]["scale"].astype(f32)
        return y @ p["out_proj"].astype(f32), s


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim",
                                   "block"))
def attention_mixer(h, p, *, n_heads, n_kv_heads, head_dim, block):
    """Full causal attention without positional rotation over ``h (T, d)``
    (already normed), in blocks of query positions: ``(T, d)``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t = h.shape[0]
        pos = jnp.arange(t)
        qkv = h @ p["wqkv"].astype(f32)
        nq, nk = n_heads * head_dim, n_kv_heads * head_dim
        q = qkv[:, :nq].reshape(t, n_heads, head_dim)
        k = qkv[:, nq:nq + nk].reshape(t, n_kv_heads, head_dim)
        v = qkv[:, nq + nk:].reshape(t, n_kv_heads, head_dim)
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
        return jnp.concatenate(outs, 0).reshape(t, -1) @ p["wo"].astype(f32)


@jax.jit
def _relu2(h, up, down):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        return jnp.square(jax.nn.relu(h @ up.astype(f32))) @ down.astype(f32)


@partial(jax.jit, static_argnames=("eps", "top_k", "scale", "norm"))
def _route(x, ln, router, bias, *, eps, top_k, scale, norm):
    """``(norm(x), chosen (T, k), weights (T, k))`` over ALL the router's
    columns."""
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, ln, eps)
        s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
        # the k largest of s + bias, by a stable sort: ties to the lower id
        chosen = jnp.argsort(-(s + bias.astype(jnp.float32)), axis=-1,
                             stable=True)[:, :top_k]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if norm:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return h, chosen, w * scale


@jax.jit
def _add_expert(out, h, idx, wts, w1, w2):
    """``out[idx] += wts * relu2_e(h[idx])``."""
    return out.at[idx].add(_relu2(h[idx], w1, w2) * wts[:, None])


def moe(x, p, *, eps, top_k, scale, norm, first, shared=True, counts=None):
    """``moe(norm(x))``: a loop over the experts held here (``p["moe"]
    ["w1"]`` holds experts ``first ..``), plus the shared expert.  ``counts``
    (a list) takes the layer's assignments a column of the router."""
    m = p["moe"]
    h, chosen, w = _route(x, p["ln2"]["scale"], m["router"], m["bias"],
                          eps=eps, top_k=top_k, scale=scale, norm=norm)
    out = (_relu2(h, p["shared"]["w1"], p["shared"]["w2"]) if shared
           else jnp.zeros_like(x))
    chosen, w = np.asarray(chosen), np.asarray(w)
    if counts is not None:
        counts.append(np.bincount(chosen.reshape(-1),
                                  minlength=m["router"].shape[-1]))
    for e in range(m["w1"].shape[0]):            # one held expert at a time
        rows, slot = np.nonzero(chosen == first + e)
        if rows.size == 0:
            continue
        # padded to a power of two with weight 0 (on row 0), so that the
        # jitted product compiles for a handful of sizes, not for every one
        n = max(8, 1 << int(rows.size - 1).bit_length())
        idx, wts = np.zeros(n, np.int32), np.zeros(n, np.float32)
        idx[:rows.size], wts[:rows.size] = rows, w[rows, slot]
        out = _add_expert(out, h, idx, wts, m["w1"][e], m["w2"][e])
    return out


@partial(jax.jit, static_argnames=("eps",))
def _head(x_last, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale, eps) @ lm_head.astype(jnp.float32)


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys and of the
    share the configuration states (``share``: the first expert held)."""
    n_layers = int(config["num_hidden_layers"])
    return dict(pattern=str(config["hybrid_override_pattern"])[:n_layers],
                eps=float(config["layer_norm_epsilon"]),
                n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                head_dim=int(config["head_dim"]),
                m_heads=int(config["mamba_num_heads"]),
                m_head_dim=int(config["mamba_head_dim"]),
                groups=int(config["n_groups"]),
                state=int(config["ssm_state_size"]),
                top_k=int(config["num_experts_per_tok"]),
                scale=float(config["routed_scaling_factor"]),
                norm=bool(config["norm_topk_prob"]),
                first=int(config.get("share", {}).get("first_expert", 0)))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, pattern: str, eps: float, n_heads: int, n_kv_heads: int,
                head_dim: int, m_heads: int, m_head_dim: int, groups: int,
                state: int, top_k: int, scale: float, norm: bool, first: int,
                block: int = 256, stores: bool = False):
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``; with ``stores`` also what a server
    would hold after it: ``(logits, {"state": the Mamba-2 layers' states
    after the last token (layers, H, P, N), "routes": the expert layers'
    assignments a column of the router (layers, E)})``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    states, routes = [], []
    for i, letter in enumerate(pattern):
        p = params[f"layer{i}"]
        if letter == "E":
            if "moe" not in p:
                raise ValueError(f"layer {i}: the weights and the published "
                                 "pattern disagree on its kind")
            x = x + moe(x, p, eps=eps, top_k=top_k, scale=scale, norm=norm,
                        first=first, counts=routes if stores else None)
            continue
        if ("mamba2" in p) != (letter == "M"):
            raise ValueError(f"layer {i}: the weights and the published "
                             "pattern disagree on its mixer")
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        if letter == "M":
            mixed, s = mamba2_mixer(h, p["mamba2"], eps=eps, heads=m_heads,
                                    head_dim=m_head_dim, groups=groups,
                                    state=state)
            if stores:
                states.append(np.asarray(s))
        else:
            mixed = attention_mixer(
                h, {k: p[k] for k in ("wqkv", "wo")}, n_heads=n_heads,
                n_kv_heads=n_kv_heads, head_dim=head_dim, block=block)
        x = x + mixed
    logits = np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                              params["lm_head"], eps=eps), np.float32)
    if stores:
        return logits, {"state": np.stack(states), "routes": np.stack(routes)}
    return logits


def store_errors(state, routes, want: Dict[str, np.ndarray]
                 ) -> Dict[str, float]:
    """What the server holds after a stream against what the reference
    would hold (``last_logits(..., stores=True)``): ``state (H, P, N)`` is
    the FIRST Mamba-2 layer's state of the stream's lane, ``routes (layers,
    E)`` the assignments the server's expert layers counted over the
    stream's tokens.  ``state_err``: the Frobenius norm of the difference
    over the reference's; ``route_err``: the share of the FIRST expert
    layer's assignments made differently (half the L1 distance of the two
    histograms over their sum), ``route_err_all`` the same over every
    layer (reported, judges nothing: deeper layers carry what the layers
    before them flipped).  ``state_err_low`` / ``state_err_min``: the lower
    quartile and the least, over the heads, of a head's own difference over
    its own norm (STATE_TOLERANCE judges the first: the heads that remember
    longest tell a rounded store from rounded activations)."""
    ref = want["state"][0].astype(np.float64)
    if state.shape != ref.shape or routes.shape != want["routes"].shape:
        raise ValueError(f"served stores {state.shape}, {routes.shape} "
                         f"against the reference's {ref.shape}, "
                         f"{want['routes'].shape}")
    off = np.abs(routes.astype(np.int64) - want["routes"])
    # a head's own error: its state's difference over its state's norm
    heads = (np.linalg.norm((state - ref).reshape(len(ref), -1), axis=1)
             / np.linalg.norm(ref.reshape(len(ref), -1), axis=1))
    return {"state_err": float(np.linalg.norm(state - ref)
                               / np.linalg.norm(ref)),
            "state_err_low": float(np.quantile(heads, 0.25)),
            "state_err_min": float(heads.min()),
            "route_err": float(off[0].sum() / 2 / want["routes"][0].sum()),
            "route_err_all": float(off.sum() / 2 / want["routes"].sum())}


def token_errors(params: Dict[str, Any], prompt: Sequence[int],
                 tokens: Sequence[int], logprobs: Sequence[float],
                 stores=None, **hyper) -> Dict[str, Any]:
    """One served greedy stream against the reference: one forward over
    ``prompt + tokens[:-1]``, whose last ``len(tokens)`` logit rows predict
    ``tokens``.  Per token: ``logprob_err``, the served log-probability
    against the reference's, and ``argmax_gap``, the reference's largest
    logit minus its logit of the emitted token.  With ``stores`` (``(state,
    routes)`` the server held once the stream had ended: every token of that
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
    streams carry them, ``state_err_low`` and ``route_err`` are the MEDIANS
    over the streams (what STATE_TOLERANCE and ROUTE_TOLERANCE judge), as
    are ``state_err``, ``state_err_min`` and ``route_err_all`` beside them."""
    err = np.concatenate([s["logprob_err"] for s in streams])
    gap = np.concatenate([s["argmax_gap"] for s in streams])
    out = {
        "logprob_err": float(np.quantile(err, QUANTILE)),
        "argmax_gap": float(np.quantile(gap, QUANTILE)),
        "logprob_err_median": float(np.median(err)),
        "logprob_err_max": float(err.max()),
        "flipped_share": float((err > 0.05).mean()),
    }
    for name in ("state_err", "state_err_low", "state_err_min", "route_err",
                 "route_err_all"):
        if all(name in s for s in streams):
            out[name] = float(np.median([s[name] for s in streams]))
    return out


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """:func:`summary` of one stream alone."""
    return summary([token_errors(params, prompt, tokens, logprobs, **hyper)])
