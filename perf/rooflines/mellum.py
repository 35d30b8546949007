"""Operations and bytes of what kind ``mellum`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a share the same way.
``round_flops`` feeds ``swa.round_mfu`` and ``round_bytes`` stands beside
it, so that a reader sees which bound a round is under.  (A decode step's
bytes and ``swa.decode_roofline`` come with a cell whose traced tail holds a
decode block: this kind's one cell has none, ROADMAP W18.)  A layer is one GQA attention (``W_q``,
``W_k``, ``W_v``, ``W_o``; K/V rows of ``num_key_value_heads`` heads in
pages) of one of two KINDS and one block of softmax-routed experts: a FULL
layer reads every key at or before the row, a WINDOW layer the
``sliding_window`` keys that end at it, so the two count their keys apart.
No kernel is added: the attention is the ragged K/V kernels' (with the
window as the walk's lower bound) and the experts are ``grouped_matmul``'s,
whose shares other metrics read.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states


def layers_by_kind(c: Dict[str, Any]):
    """``(full, window)``: the layers of each attention kind."""
    kinds = list(c["layer_types"])[:int(c["num_hidden_layers"])]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def widths(c: Dict[str, Any]):
    """``(q, kv)``: the query's and the key's (= the value's) row width."""
    d = int(c["head_dim"])
    return (int(c["num_attention_heads"]) * d,
            int(c["num_key_value_heads"]) * d)


def attention_params(c: Dict[str, Any]) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` of one layer (norm scales left
    out)."""
    hidden = int(c["hidden_size"])
    q, kv = widths(c)
    return hidden * (q + 2 * kv) + q * hidden


def router_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["num_experts"])


def expert_params(c: Dict[str, Any]) -> int:
    """One expert: gate, up, down."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def outside_expert_params(c: Dict[str, Any]) -> int:
    """What every row reads whatever the routing: every layer's attention
    and router."""
    return int(c["num_hidden_layers"]) * (attention_params(c)
                                          + router_params(c))


def head_params(c: Dict[str, Any]) -> int:
    """The output head (a matrix of its own; the table's rows a step
    gathers are left out)."""
    return int(c["vocab_size"]) * int(c["hidden_size"])


def model_params(c: Dict[str, Any]) -> int:
    """Everything the chip holds (norm scales left out): the layers, the
    table and the head."""
    return (outside_expert_params(c) + int(c["num_hidden_layers"])
            * int(c["num_experts"]) * expert_params(c) + 2 * head_params(c))


def kv_bytes_per_token(c: Dict[str, Any]) -> Dict[str, int]:
    """The K and V rows a token leaves, by page group: ``full`` a token of
    context, ``window`` a ROW of a lane's window blocks."""
    full, window = layers_by_kind(c)
    row = 2 * widths(c)[1] * BYTES
    return {"full": full * row, "window": window * row}


def decode_kv_bytes(c: Dict[str, Any], lanes: float, context: float,
                    window_context: float) -> float:
    """The K/V rows a decode step reads: ``context`` keys a lane on the full
    layers, ``window_context`` (the mean of ``min(context, window)``) on the
    window layers."""
    per = kv_bytes_per_token(c)
    return lanes * (context * per["full"] + window_context * per["window"])


def round_bytes(c: Dict[str, Any], lanes: float, context: float,
                window_context: float) -> float:
    """Bytes one mixed round has to move: every weight but the table once
    (512 prompt rows at top-8 of 64 reach every expert) and the K/V rows of
    the lanes that had a segment.  A LOWER bound of a round's traffic:
    embedding rows, norm scales and the activations are left out."""
    return (BYTES * (model_params(c) - head_params(c))
            + decode_kv_bytes(c, lanes, context, window_context))


def attention_pair_flops(c: Dict[str, Any]) -> int:
    """Operations ONE (query row, key) pair costs one layer: every query
    head's score and weighted sum over ``head_dim``."""
    return 4 * widths(c)[0]


def round_flops(c: Dict[str, Any], tokens: float, expert_rows: float,
                pairs: float, window_pairs: float, head_rows: float) -> float:
    """Operations the rows of one mixed round cost: ``tokens`` rows through
    every layer's projections and router (two a parameter a row),
    ``expert_rows`` (row, expert) assignments (all layers together),
    ``pairs`` (query row, key) pairs through each FULL layer's attention and
    ``window_pairs`` (a row at context ``n`` has ``min(n, window)``) through
    each WINDOW layer's, ``head_rows`` rows through the head.  Only rows
    that held a token are counted (a round also computes its padding), so a
    share of the peak computed from it cannot pass 100 %."""
    full, window = layers_by_kind(c)
    return (2.0 * tokens * outside_expert_params(c)
            + 2.0 * expert_rows * expert_params(c)
            + (pairs * full + window_pairs * window)
            * attention_pair_flops(c)
            + 2.0 * head_rows * head_params(c))
