"""Plain reference: AI21-Jamba2-3B (``model_type`` ``jamba``).

Written from the published ``config.json`` keys and the layer the model
type names (``JambaMambaMixer`` / ``JambaAttentionDecoderLayer`` of the
published modelling code, as remembered: there is no network here, so every
point below is listed under ``assumed`` in the configuration file);
straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, no chunking, nothing imported from the program.

Layer ``i`` is an attention layer iff ``i % attn_layer_period ==
attn_layer_offset``, else a Mamba layer; ``num_experts`` 1 makes every
layer's feed-forward the dense MLP.  With ``N(.)`` RMSNorm at
``rms_norm_eps``::

    x <- x + Mixer_i(N(x; input_layernorm))
    x <- x + W_down(silu(W_gate h) * W_up h),   h = N(x; pre_ff_layernorm)

*Mamba mixer*, per token ``t`` of the sequence (``d_inner = mamba_expand x
hidden_size``): ``[u, z] = h W_in``; ``u_t = silu(b_conv + sum_k w_conv[k]
* u_{t-(d_conv-1)+k})`` (depthwise, causal, zeros before the sequence);
``[r, B, C] = u_t W_x``; ``r, B, C = N(r), N(B), N(C)`` (Jamba's addition
to Mamba-1); ``dt = softplus(r W_dt + b_dt)``; ``h_t = exp(dt (x) A) *
h_{t-1} + (dt * u_t) (x) B`` with ``A = -exp(A_log)``, ``h_{-1} = 0``;
``y_t = h_t . C + D * u_t``; ``out = (y_t * silu(z_t)) W_out``.  Here: ONE
sequential ``lax.scan`` over the tokens.

*Attention mixer*: q, k, v, o without bias, ``num_attention_heads`` heads
of ``hidden_size / num_attention_heads`` on ``num_key_value_heads`` KV
heads, NO positional encoding, scale ``head_dim ** -0.5``, causal, full.

Output: ``N(x; final_layernorm) @ embed^T`` (``tie_word_embeddings``).

It is handed the *served* weights (bf16, the program's layout, which the
program documents in ``tpulab/models/spec.py``: ``wqkv`` is ``[q | k | v]``
column-wise, ``w1`` the gate, ``w3`` the up and ``w2`` the down projection;
under ``mamba``: ``in_proj`` ``[u | z]``, ``conv_w (d_conv, d_inner)`` with
tap ``k`` on the input ``d_conv - 1 - k`` tokens back, ``x_proj`` ``[r | B |
C]``, ``a_log (d_state, d_inner)``) and upcasts one layer at a time inside
that layer's jitted function.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens asked of each reference-check stream: thirty-two, so that a
#: stream decodes well past the chunks of its prompt, through the state.
REFERENCE_STEPS = 32

#: Largest difference allowed between the served path and this reference,
#: in logit units (natural log), on (a) the log-probability of every emitted
#: token and (b) how far the emitted token's reference logit may lie under
#: the reference's largest; the LARGEST over a stream's tokens, as the dense
#: kind has it (nothing here is discontinuous, as a router is).
#:
#: Its size, from the readings below (PERF.md section 6, PR 32: on the v5e,
#: at the published widths, through the Generate RPC under the cell's engine
#: sizes, prompts of 24 and 600 tokens).  bf16 serving read
#: TOLERANCE_READINGS["bf16"] over ten seeds and both prompts, the dense
#: kind's own band (0.04-0.17), so the dense kind's limit with the same room
#: above it.  What it catches, two seeds each: the state NOT carried across a
#: chunk boundary (a later chunk of a prompt starts from zeros) fails on the
#: 600-token prompt and leaves the 24-token prompt, one chunk, alone; the SSM
#: state kept in fp8 (e4m3: the precision below the bf16 the configuration
#: states) fails on both prompts.  What it cannot catch: the SSM state
#: rounded to bf16 between dispatches reads inside bf16 serving's own band:
#: within 32 decode steps a rounding of 2^-9 a step, on a state that is one
#: addend of ``y``, stays under what bf16 activations already cost (PERF.md
#: section 7).  (Emulate a narrower state with ``jax.lax.reduce_precision``:
#: XLA removes a float32 -> bf16 -> float32 ``astype`` pair.)
TOLERANCE = 0.25
TOLERANCE_READINGS = {"bf16": "0.076-0.165",
                      "state_dropped_at_chunk": "0.921-3.005",
                      "fp8_state": "0.946-2.314",
                      "bf16_state": "0.103-0.138"}


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def mamba_mixer(h, p, *, eps):
    """The Mamba mixer over the whole sequence ``h (T, d)`` (already
    normed), from zeros."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        w_conv = p["conv_w"].astype(f32)                   # (K, d_inner)
        k, d_inner = w_conv.shape
        n = p["a_log"].shape[0]
        r = p["dt_proj"].shape[0]
        t = h.shape[0]
        uz = h @ p["in_proj"].astype(f32)
        u, z = uz[:, :d_inner], uz[:, d_inner:]
        padded = jnp.concatenate([jnp.zeros((k - 1, d_inner), f32), u], 0)
        u = jax.nn.silu(p["conv_b"].astype(f32) + sum(
            w_conv[j] * padded[j:j + t] for j in range(k)))
        xp = u @ p["x_proj"].astype(f32)
        dt = jax.nn.softplus(
            _rmsnorm(xp[:, :r], p["dt_norm"]["scale"], eps)
            @ p["dt_proj"].astype(f32) + p["dt_bias"].astype(f32))
        b = _rmsnorm(xp[:, r:r + n], p["b_norm"]["scale"], eps)
        c = _rmsnorm(xp[:, r + n:], p["c_norm"]["scale"], eps)
        a = -jnp.exp(p["a_log"].astype(f32))               # (n, d_inner)

        def step(state, row):
            u_t, dt_t, b_t, c_t = row
            state = (jnp.exp(dt_t[None, :] * a) * state
                     + (dt_t * u_t)[None, :] * b_t[:, None])
            return state, (state * c_t[:, None]).sum(0)

        _, y = jax.lax.scan(step, jnp.zeros((n, d_inner), f32),
                            (u, dt, b, c))
        y = y + p["d"].astype(f32) * u
        return (y * jax.nn.silu(z)) @ p["out_proj"].astype(f32)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "block"))
def attention_mixer(h, wqkv, wo, *, n_heads, n_kv_heads, block):
    """Full causal attention over ``h (T, d)`` (already normed), no
    positional encoding, in blocks of query positions."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        t, d = h.shape
        hd = d // n_heads
        g = n_heads // n_kv_heads
        qkv = h @ wqkv.astype(f32)
        q = qkv[:, :n_heads * hd].reshape(t, n_kv_heads, g, hd)
        k = qkv[:, n_heads * hd:(n_heads + n_kv_heads) * hd].reshape(
            t, n_kv_heads, hd)
        v = qkv[:, (n_heads + n_kv_heads) * hd:].reshape(t, n_kv_heads, hd)
        pos = jnp.arange(t)
        outs = []
        for s in range(0, t, block):
            e = min(s + block, t)
            scores = jnp.einsum("qhgd,khd->hgqk", q[s:e], k[:e]) / np.sqrt(hd)
            mask = pos[s:e, None] >= pos[None, :e]
            probs = jax.nn.softmax(
                jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hgqk,khd->qhgd", probs, v[:e])
                        .reshape(e - s, d))
        return jnp.concatenate(outs, 0) @ wo.astype(f32)


@partial(jax.jit, static_argnames=("eps",))
def _mlp(x, ln2, gate, up, down, *, eps):
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        h = _rmsnorm(x, ln2, eps)
        return x + (jax.nn.silu(h @ gate.astype(f32))
                    * (h @ up.astype(f32))) @ down.astype(f32)


def layer(x, p, *, eps, n_heads, n_kv_heads, block=256):
    """One decoder layer over the whole sequence ``x (T, d)``, float32: a
    Mamba layer where ``p`` has ``mamba``, else an attention layer."""
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    if "mamba" in p:
        x = x + mamba_mixer(h, p["mamba"], eps=eps)
    else:
        x = x + attention_mixer(h, p["wqkv"], p["wo"], n_heads=n_heads,
                                n_kv_heads=n_kv_heads, block=block)
    return _mlp(x, p["ln2"]["scale"], p["w1"], p["w3"], p["w2"], eps=eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(x_last, scale, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x_last, scale, eps) @ embed.astype(jnp.float32).T


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What :func:`last_logits` needs of the published keys.  Which layers
    are Mamba layers it reads off the weights."""
    period, offset = (int(config["attn_layer_period"]),
                      int(config["attn_layer_offset"]))
    n_layers = int(config["num_hidden_layers"])
    return dict(n_layers=n_layers,
                rms_norm_eps=float(config["rms_norm_eps"]),
                n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                attention_layers=tuple(i for i in range(n_layers)
                                       if i % period == offset))


def last_logits(params: Dict[str, Any], tokens: Sequence[int], n_last: int,
                *, n_layers: int, rms_norm_eps: float, n_heads: int,
                n_kv_heads: int, attention_layers: Sequence[int]
                ) -> np.ndarray:
    """Float32 logits (n_last, vocab) at the last ``n_last`` positions of one
    full forward pass over ``tokens``."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    x = params["embed"][toks].astype(jnp.float32)
    for i in range(n_layers):
        p = params[f"layer{i}"]
        if ("mamba" in p) == (i in attention_layers):
            raise ValueError(f"layer {i}: the weights and the published "
                             "layer order disagree on its mixer")
        x = layer(x, p, eps=rms_norm_eps, n_heads=n_heads,
                  n_kv_heads=n_kv_heads)
    return np.asarray(_head(x[-n_last:], params["final_norm"]["scale"],
                            params["embed"], eps=rms_norm_eps), np.float32)


def compare(params: Dict[str, Any], prompt: Sequence[int],
            tokens: Sequence[int], logprobs: Sequence[float],
            **hyper) -> Dict[str, float]:
    """Hold a served greedy stream to the reference.  The reference runs
    one forward over ``prompt + tokens[:-1]``; row ``i`` of its last
    ``len(tokens)`` logit rows predicts ``tokens[i]``.  Returns the largest
    ``logprob_err`` (served log-probability against the reference's) and
    ``argmax_gap`` (reference's largest logit minus its logit of the token
    that was emitted: 0 where the two agree on the argmax) over the
    tokens, and the median error beside them (which judges nothing)."""
    n = len(tokens)
    logits = last_logits(params, list(prompt) + list(tokens[:-1]), n, **hyper)
    logits = logits.astype(np.float64)
    m = logits.max(-1, keepdims=True)
    logp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    rows = np.arange(n)
    toks = np.asarray(tokens)
    err = np.abs(logp[rows, toks] - np.asarray(logprobs))
    return {"logprob_err": float(err.max()),
            "argmax_gap": float((logits.max(-1) - logits[rows, toks]).max()),
            "logprob_err_median": float(np.median(err))}
