"""Operations and bytes of what kind ``keye_vl2`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a roofline share the same
way.  ``decode_step_bytes`` feeds ``dsa.decode_roofline``;
``index_scores_cost`` and ``sparse_attention_cost`` are what the indexer and
the attention over the selected keys need, whatever implements them (the
kernels ``dsa_index_scores`` and ``sparse_paged_attention`` today), whose
shares of the roofline the benchmark cannot read yet (``reduce_trace`` keeps
ten operations; PERF.md section 7) and PERF.md reports from a full trace.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2       # bf16, the precision the configuration states


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one layer's attention (norm scales left out)."""
    d, h, hkv = (int(c["hidden_size"]), int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]))
    hd = int(c["head_dim"])
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def indexer_params(c: Dict[str, Any]) -> int:
    """Index queries, the one index key and the heads' weights (the key's
    LayerNorm left out)."""
    d, sa = int(c["hidden_size"]), c["sa_config"]
    hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    return d * hi * di + d * di + d * hi


def expert_params(c: Dict[str, Any]) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def layer_params(c: Dict[str, Any]) -> int:
    """Every parameter of one layer, all experts (norm scales left out)."""
    return (attention_params(c) + indexer_params(c)
            + int(c["hidden_size"]) * int(c["num_experts"])
            + int(c["num_experts"]) * expert_params(c))


def embedding_params(c: Dict[str, Any]) -> int:
    """Embedding and untied output head."""
    return 2 * int(c["vocab_size"]) * int(c["hidden_size"])


def decode_step_bytes(c: Dict[str, Any], lanes: float, mean_ctx: float,
                      experts_hit: float) -> float:
    """Bytes one decode step has to move: the weights it touches (every
    layer's attention, indexer and router, the ``experts_hit`` routed
    experts a layer that at least one row chose, the output head) and, for
    each of ``lanes`` live lanes at a context of ``mean_ctx`` keys, a layer's
    index keys (one of ``indexer_head_dim`` values a key, unpadded) and the K
    and V rows of the ``min(mean_ctx, topk)`` selected keys.  A LOWER bound
    of a step's traffic (embedding rows, norms, activations, the padding of
    an index row and every K/V row a page walk reads beside the selected ones
    are left out), so a share of the roofline computed from it cannot pass
    100 %."""
    d, sa = int(c["hidden_size"]), c["sa_config"]
    n_layers = int(c["num_hidden_layers"])
    weights = (n_layers * (attention_params(c) + indexer_params(c)
                           + d * int(c["num_experts"])
                           + experts_hit * expert_params(c))
               + d * int(c["vocab_size"]))
    kv_row = 2 * int(c["num_key_value_heads"]) * int(c["head_dim"])
    cache = n_layers * lanes * (
        mean_ctx * int(sa["indexer_head_dim"])
        + min(mean_ctx, int(sa["topk"])) * kv_row)
    return BYTES * (weights + cache)


def index_scores_cost(c: Dict[str, Any], rows: int, ctx: float
                      ) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of ONE layer's index scores for ``rows`` query
    rows of one lane against ``ctx`` keys each (a chunk's rows share the
    lane's keys: read once): a dot of ``indexer_head_dim`` a head a key, the
    relu and the weighted sum over heads; bytes are the index keys, the
    queries and weights in, the float32 scores out."""
    sa = c["sa_config"]
    hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    return {"flops": rows * ctx * hi * (2.0 * di + 3),
            "bytes": BYTES * (ctx * di + rows * hi * di) + 4.0 * rows * (
                hi + ctx)}


def sparse_attention_cost(c: Dict[str, Any], rows: int, ctx: float
                          ) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of ONE layer's attention for ``rows`` query
    rows of one lane, each over its ``min(ctx, topk)`` selected keys: scores
    and values over ``head_dim`` for every query head; bytes are the K and V
    rows of the keys at least one row selected (at most ``ctx``, at least
    ``min(ctx, topk)``; the lower is counted), the queries in and the
    outputs out."""
    h, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    kept = min(ctx, int(c["sa_config"]["topk"]))
    kv_row = 2 * int(c["num_key_value_heads"]) * hd
    return {"flops": 4.0 * rows * kept * h * hd,
            "bytes": BYTES * (kept * kv_row + 2 * rows * h * hd)}
