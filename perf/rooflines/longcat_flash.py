"""Operations and bytes of what kind ``longcat_flash`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a share the same way.
``decode_step_bytes`` feeds ``scmoe.decode_roofline``; ``round_flops`` feeds
``scmoe.round_mfu`` and ``round_bytes`` stands beside it, so that a reader
sees which bound a round is under.  ``n_routed_experts`` of the configuration
is what this chip HOLDS; the router's width is ``share.n_routed_experts +
zero_expert_num``.  A published layer is two latent attentions, two dense
FFNs, one router and the held experts.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of ONE latent attention (norm scales left out): ``q_a``,
    ``q_b``, ``kv_a``, ``kv_b``, ``o``."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    ql, kl = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    nope, rope, v = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                     int(c["v_head_dim"]))
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def dense_ffn_params(c: Dict[str, Any]) -> int:
    """ONE dense SwiGLU FFN: gate, up, down."""
    return 3 * int(c["hidden_size"]) * int(c["ffn_hidden_size"])


def expert_params(c: Dict[str, Any]) -> int:
    """One routed FFN expert: gate, up, down."""
    return 3 * int(c["hidden_size"]) * int(c["expert_ffn_hidden_size"])


def router_columns(c: Dict[str, Any]) -> int:
    """FFN experts of the whole layer and the identity experts behind them."""
    return int(c["share"]["n_routed_experts"]) + int(c["zero_expert_num"])


def layer_outside_expert_params(c: Dict[str, Any]) -> int:
    """What every row reads of ONE published layer whatever the routing: two
    attentions, two dense FFNs, the router."""
    return (2 * attention_params(c) + 2 * dense_ffn_params(c)
            + int(c["hidden_size"]) * router_columns(c))


def outside_expert_params(c: Dict[str, Any]) -> int:
    return int(c["num_layers"]) * layer_outside_expert_params(c)


def head_params(c: Dict[str, Any]) -> int:
    """The untied output head over the slice of the vocabulary held here."""
    return int(c["vocab_size"]) * int(c["hidden_size"])


def model_params(c: Dict[str, Any]) -> int:
    """Everything this chip holds: the layers outside the experts, the held
    experts of every layer, the embedding and the head."""
    return (outside_expert_params(c) + int(c["num_layers"])
            * int(c["n_routed_experts"]) * expert_params(c)
            + 2 * head_params(c))


def latent_layers(c: Dict[str, Any]) -> int:
    """Layers of the latent page store: two a published layer."""
    return 2 * int(c["num_layers"])


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """The latent rows a token leaves, all layers: ``[c_kv ; k_rope]`` of
    content (the page store pads a row to whole 128-lane tiles)."""
    return latent_layers(c) * (int(c["kv_lora_rank"])
                               + int(c["qk_rope_head_dim"])) * BYTES


def decode_step_bytes(c: Dict[str, Any], lanes: float, experts_hit: float,
                      context: float) -> float:
    """Bytes one decode step has to move: the weights outside the experts
    once, the held experts that a row chose (``experts_hit`` a layer, the
    mean), the head, and the latent rows of the ``lanes`` that ran the step
    (``context`` tokens a lane).  A LOWER bound of a step's traffic:
    embedding rows, norm scales, activations and the rows' padding are left
    out, so a share of the roofline computed from it cannot pass 100 %."""
    weights = (outside_expert_params(c) + int(c["num_layers"]) * experts_hit
               * expert_params(c) + head_params(c))
    return BYTES * weights + lanes * context * kv_bytes_per_token(c)


def round_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """Bytes one mixed round has to move: every held weight once (512 prompt
    tokens at top-12 of 768 reach every held expert), the head, and the
    latent rows of the lanes that had a segment.  A lower bound, as above."""
    weights = model_params(c) - head_params(c)          # no embedding
    return BYTES * weights + lanes * context * kv_bytes_per_token(c)


def attention_pair_flops(c: Dict[str, Any]) -> int:
    """Operations ONE (query row, key) pair costs ONE latent attention in
    the absorbed form: every head's score over the latent row's ``kv_lora +
    rope`` values and its weighted sum over ``kv_lora``."""
    kl, rope = int(c["kv_lora_rank"]), int(c["qk_rope_head_dim"])
    return 2 * int(c["num_attention_heads"]) * ((kl + rope) + kl)


def round_flops(c: Dict[str, Any], tokens: float, expert_rows: float,
                pairs: float, head_rows: float) -> float:
    """Operations the rows of one mixed round cost: ``tokens`` rows through
    every projection, both dense FFNs and the router of every layer (two a
    parameter a row), ``expert_rows`` (row, expert) assignments that landed
    on held experts (all expert layers together), ``pairs`` (query row,
    key) pairs through each of the latent attentions, ``head_rows`` rows
    through the head.  Only rows that held a token are counted (a round
    also computes its padding), so a share of the peak computed from it
    cannot pass 100 %."""
    return (2.0 * tokens * outside_expert_params(c)
            + 2.0 * expert_rows * expert_params(c)
            + pairs * latent_layers(c) * attention_pair_flops(c)
            + 2.0 * head_rows * head_params(c))
