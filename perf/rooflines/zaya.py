"""Operations and bytes of what kind ``zaya`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a share the same way.
``decode_step_bytes`` feeds ``cca.decode_roofline`` and, with
``decode_kv_bytes``, ``cca.decode_kv_share``; ``round_flops`` feeds
``cca.round_mfu`` and ``round_bytes`` stands beside it, so that a reader
sees which bound a round is under.  A layer is one compressed convolutional
attention (five projections, two convolutions over ``[q ; k]``, K/V rows of
``num_key_value_heads`` heads in pages, three tails in the lane's slot) and
one expert block behind an MLP router whose last column is a skip column.
No kernel is added: the attention is the ragged K/V kernels' and the experts
are ``grouped_matmul``'s, whose shares other metrics read.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states


def latent_widths(c: Dict[str, Any]):
    """``(q, kv)``: the query's and the key's (= the value's) latent width."""
    d = int(c["head_dim"])
    return (int(c["num_attention_heads"]) * d,
            int(c["num_key_value_heads"]) * d)


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one CCA (norm scales and ``tau`` left out): ``W_q``,
    ``W_k``, ``W_v1``, ``W_v2``, ``W_o`` and the two convolutions with their
    biases."""
    hidden, d = int(c["hidden_size"]), int(c["head_dim"])
    q, kv = latent_widths(c)
    heads = (q + kv) // d
    return (hidden * (q + 2 * kv) + q * hidden
            + (int(c["cca_time0"]) + 1) * (q + kv)
            + int(c["cca_time1"]) * heads * d * d + (q + kv))


def router_params(c: Dict[str, Any]) -> int:
    """The MLP router: the down-projection, ``gamma``, the norm, two hidden
    layers with biases, the projection onto ``num_experts + 1`` columns and
    the selection bias."""
    hidden, w = int(c["hidden_size"]), int(c["router_hidden_size"])
    cols = int(c["num_experts"]) + 1
    return hidden * w + 3 * w + 2 * (w * w + w) + w * cols + cols


def expert_params(c: Dict[str, Any]) -> int:
    """One expert: gate, up, down."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def outside_expert_params(c: Dict[str, Any]) -> int:
    """What every row reads whatever the routing: every layer's attention,
    router and residual scaling vectors."""
    return int(c["num_hidden_layers"]) * (
        attention_params(c) + router_params(c) + 8 * int(c["hidden_size"]))


def head_params(c: Dict[str, Any]) -> int:
    """The tied table: the embedding and the head are one matrix."""
    return int(c["vocab_size"]) * int(c["hidden_size"])


def model_params(c: Dict[str, Any]) -> int:
    """Everything the chip holds (norm scales left out)."""
    return (outside_expert_params(c) + int(c["num_hidden_layers"])
            * int(c["num_experts"]) * expert_params(c) + head_params(c))


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """The K and V rows a token leaves, all layers."""
    return int(c["num_hidden_layers"]) * 2 * latent_widths(c)[1] * BYTES


def state_bytes_per_lane(c: Dict[str, Any]) -> int:
    """What a lane keeps beside its pages, all layers: ``cca_time0 - 1``
    rows of ``c``, ``cca_time1 - 1`` rows of ``a`` and the shifted half of
    the value."""
    q, kv = latent_widths(c)
    return int(c["num_hidden_layers"]) * BYTES * (
        (int(c["cca_time0"]) + int(c["cca_time1"]) - 2) * (q + kv) + kv // 2)


def decode_kv_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """The K/V rows a decode step reads: ``context`` tokens of ``lanes``."""
    return lanes * context * kv_bytes_per_token(c)


def decode_step_bytes(c: Dict[str, Any], lanes: float, experts_hit: float,
                      context: float) -> float:
    """Bytes one decode step has to move: the weights outside the experts
    once, the experts that a row chose (``experts_hit`` a layer, the mean),
    the table once (as the head), the K/V rows of the ``lanes`` that ran the
    step (``context`` tokens a lane) and their tails read and written.  A
    LOWER bound of a step's traffic: embedding rows, norm scales and the
    activations are left out, so a share of the roofline computed from it
    cannot pass 100 %."""
    weights = (outside_expert_params(c) + int(c["num_hidden_layers"])
               * experts_hit * expert_params(c) + head_params(c))
    return (BYTES * weights + decode_kv_bytes(c, lanes, context)
            + 2 * lanes * state_bytes_per_lane(c))


def round_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """Bytes one mixed round has to move: every weight once (512 prompt
    rows at top-1 of 17 reach every expert), the table as the head, the K/V
    rows of the lanes that had a segment.  A lower bound, as above."""
    return (BYTES * model_params(c) + decode_kv_bytes(c, lanes, context)
            + 2 * lanes * state_bytes_per_lane(c))


def attention_pair_flops(c: Dict[str, Any]) -> int:
    """Operations ONE (query row, key) pair costs one CCA: every query
    head's score and weighted sum over ``head_dim``."""
    return 4 * latent_widths(c)[0]


def round_flops(c: Dict[str, Any], tokens: float, expert_rows: float,
                pairs: float, head_rows: float) -> float:
    """Operations the rows of one mixed round cost: ``tokens`` rows through
    every layer's projections, convolutions and router (two a parameter a
    row), ``expert_rows`` (row, expert) assignments that landed on an FFN
    expert (all layers together; the skip column costs none), ``pairs``
    (query row, key) pairs through each layer's attention, ``head_rows``
    rows through the head.  Only rows that held a token are counted (a
    round also computes its padding), so a share of the peak computed from
    it cannot pass 100 %."""
    return (2.0 * tokens * outside_expert_params(c)
            + 2.0 * expert_rows * expert_params(c)
            + pairs * int(c["num_hidden_layers"]) * attention_pair_flops(c)
            + 2.0 * head_rows * head_params(c))
