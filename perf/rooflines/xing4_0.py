"""Operations and bytes of what kind ``xing4_0`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a share the same way.
``decode_step_bytes`` feeds ``mhc.decode_roofline``; ``round_flops`` feeds
``mhc.round_mfu`` and ``round_bytes`` stands beside it, so that a reader sees
which bound a round is under; ``mhc_cost`` is the hyper-connections' own part
of either (the two scopes ``mhc_pre`` / ``mhc_post`` of a step program),
whose share the benchmark cannot read yet (``reduce_trace`` keeps ten
operations; PERF.md section 7) and PERF.md reports from a full trace.  A
layer is one latent attention and one FFN (dense SwiGLU in the first
``first_k_dense_replace`` layers, else a router, a shared expert and the
routed experts), each inside a hyper-connection over ``hc_mult`` streams.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one latent attention (norm scales left out): ``q_a``,
    ``q_b``, ``kv_a``, ``kv_b``, ``o``."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    ql, kl = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    nope, rope, v = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                     int(c["v_head_dim"]))
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def dense_ffn_params(c: Dict[str, Any]) -> int:
    """The dense SwiGLU FFN of a leading layer: gate, up, down."""
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def expert_params(c: Dict[str, Any]) -> int:
    """One routed (or shared) expert: gate, up, down."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def mhc_maps(c: Dict[str, Any]) -> int:
    """Numbers a token's hyper-connection computes a sublayer: ``h_pre``,
    ``h_post`` and ``H_res``, ``2 n + n^2``."""
    n = int(c["hc_mult"])
    return 2 * n + n * n


def mhc_params(c: Dict[str, Any]) -> int:
    """One sublayer's hyper-connection: ``phi (n C, 2 n + n^2)``, the norm
    scale over ``n C``, the biases and the three scalars."""
    nc = int(c["hc_mult"]) * int(c["hidden_size"])
    return nc * mhc_maps(c) + nc + mhc_maps(c) + 3


def sublayers(c: Dict[str, Any]) -> int:
    """Hyper-connected sublayers a forward: two a layer."""
    return 2 * int(c["num_hidden_layers"])


def expert_layers(c: Dict[str, Any]) -> int:
    return int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"])


def outside_expert_params(c: Dict[str, Any]) -> int:
    """What every row reads whatever the routing: every layer's attention
    and two hyper-connections, the dense layers' FFN, an expert layer's
    router and shared expert."""
    d = int(c["hidden_size"])
    n_layers, n_dense = (int(c["num_hidden_layers"]),
                         int(c["first_k_dense_replace"]))
    return (n_layers * (attention_params(c) + 2 * mhc_params(c))
            + n_dense * dense_ffn_params(c)
            + expert_layers(c) * (d * int(c["n_routed_experts"])
                                  + int(c["n_shared_experts"])
                                  * expert_params(c)))


def head_params(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"]) * int(c["hidden_size"])


def model_params(c: Dict[str, Any]) -> int:
    """Everything the chip holds: the layers outside the routed experts,
    every routed expert, the embedding and the untied head."""
    return (outside_expert_params(c) + expert_layers(c)
            * int(c["n_routed_experts"]) * expert_params(c)
            + 2 * head_params(c))


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """The latent rows a token leaves, all layers: ``[c_kv ; k_rope]`` of
    content (the page store pads a row to whole 128-lane tiles)."""
    return int(c["num_hidden_layers"]) * (
        int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"])) * BYTES


def stream_bytes_per_row(c: Dict[str, Any]) -> int:
    """What a token row holds between sublayers: ``n x C`` values."""
    return int(c["hc_mult"]) * int(c["hidden_size"]) * BYTES


def mhc_cost(c: Dict[str, Any], rows: float) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of the hyper-connections of ONE forward over
    ``rows`` token rows, every sublayer.  Operations a row a sublayer: the
    projection of the ``n C`` normed values onto ``2 n + n^2`` numbers (``2
    n C (2 n + n^2)``), ``H_res X`` (``2 n^2 C``), the weighted sum the
    sublayer reads and the outer product it writes back (``2 n C`` each);
    the norm, the sigmoids and the Sinkhorn sweeps over ``n^2`` numbers are
    left out.  Bytes: the streams read once and written once a sublayer,
    and the sublayers' parameters once."""
    n, d = int(c["hc_mult"]), int(c["hidden_size"])
    per_row = 2 * n * d * mhc_maps(c) + 2 * n * n * d + 4 * n * d
    return {"flops": float(rows * sublayers(c) * per_row),
            "bytes": float(sublayers(c) * (2 * rows * stream_bytes_per_row(c)
                                           + BYTES * mhc_params(c)))}


def decode_step_bytes(c: Dict[str, Any], lanes: float, experts_hit: float,
                      context: float) -> float:
    """Bytes one decode step has to move: the weights outside the routed
    experts once, the routed experts that a row chose (``experts_hit`` an
    expert layer, the mean), the head, the latent rows of the ``lanes`` that
    ran the step (``context`` tokens a lane), and their streams read and
    written once a sublayer.  A LOWER bound of a step's traffic: embedding
    rows, norm scales, the other activations and the rows' padding are left
    out, so a share of the roofline computed from it cannot pass 100 %."""
    weights = (outside_expert_params(c) + expert_layers(c) * experts_hit
               * expert_params(c) + head_params(c))
    return (BYTES * weights + lanes * context * kv_bytes_per_token(c)
            + 2 * lanes * sublayers(c) * stream_bytes_per_row(c))


def round_bytes(c: Dict[str, Any], lanes: float, context: float,
                rows: float = 0.0) -> float:
    """Bytes one mixed round has to move: every held weight once (512 prompt
    rows at top-4 of 64 reach every expert), the head, the latent rows of
    the lanes that had a segment, and the ``rows``' streams read and written
    once a sublayer.  A lower bound, as above."""
    weights = model_params(c) - head_params(c)          # no embedding
    return (BYTES * weights + lanes * context * kv_bytes_per_token(c)
            + 2 * rows * sublayers(c) * stream_bytes_per_row(c))


def attention_pair_flops(c: Dict[str, Any]) -> int:
    """Operations ONE (query row, key) pair costs one latent attention in
    the absorbed form: every head's score over the latent row's ``kv_lora +
    rope`` values and its weighted sum over ``kv_lora``."""
    kl, rope = int(c["kv_lora_rank"]), int(c["qk_rope_head_dim"])
    return 2 * int(c["num_attention_heads"]) * ((kl + rope) + kl)


def round_flops(c: Dict[str, Any], tokens: float, expert_rows: float,
                pairs: float, head_rows: float) -> float:
    """Operations the rows of one mixed round cost: ``tokens`` rows through
    every projection, the dense FFN, the router and the shared expert of
    every layer (two a parameter a row; the hyper-connections' ``phi`` among
    them) and through the hyper-connections' sums (:func:`mhc_cost`),
    ``expert_rows`` (row, expert) assignments (all expert layers together),
    ``pairs`` (query row, key) pairs through each layer's latent attention,
    ``head_rows`` rows through the head.  Only rows that held a token are
    counted (a round also computes its padding), so a share of the peak
    computed from it cannot pass 100 %."""
    n, d = int(c["hc_mult"]), int(c["hidden_size"])
    # phi's product is in outside_expert_params; the rest of mhc_cost here
    sums = sublayers(c) * (2 * n * n * d + 4 * n * d)
    return (tokens * (2.0 * outside_expert_params(c) + sums)
            + 2.0 * expert_rows * expert_params(c)
            + pairs * int(c["num_hidden_layers"]) * attention_pair_flops(c)
            + 2.0 * head_rows * head_params(c))
