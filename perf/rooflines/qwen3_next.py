"""Operations and bytes of what kind ``qwen3_next`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a roofline share the same
way.  ``decode_step_bytes`` feeds ``gdn.decode_roofline`` and ``round_bytes``
``gdn.round_roofline``; ``chunk_delta_rule_cost`` is the new Pallas kernel's
(``chunk_gated_delta_rule``) operations and bytes, whose share the benchmark
cannot read yet (``reduce_trace`` keeps ten operations; PERF.md section 7)
and PERF.md reports from a full trace.  ``num_experts`` of the configuration
is what this chip HOLDS; the router's width is ``share.num_experts``.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states
STATE_BYTES = 4     # the delta rule's state is float32
CHUNK = 64          # rows of a pass of the chunk kernel


def _gdn_widths(c: Dict[str, Any]):
    return (int(c["linear_num_key_heads"]) * int(c["linear_key_head_dim"]),
            int(c["linear_num_value_heads"]) * int(c["linear_value_head_dim"]),
            int(c["linear_num_value_heads"]),
            int(c["linear_conv_kernel_dim"]))


def gdn_params(c: Dict[str, Any]) -> int:
    """Parameters of one Gated DeltaNet mixer (``A_log``, ``dt_bias`` and
    the norm's scale left out): ``in_proj_qkvz``, ``in_proj_ba``, the
    convolution, ``out_proj``."""
    d = int(c["hidden_size"])
    nk, nv, hv, taps = _gdn_widths(c)
    return d * (2 * nk + 2 * nv) + d * 2 * hv + (2 * nk + nv) * taps + nv * d


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one gated-attention mixer: ``q_proj`` (query and gate),
    k, v, ``o_proj``, without bias."""
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    nq = int(c["num_attention_heads"]) * hd
    nkv = int(c["num_key_value_heads"]) * hd
    return d * 2 * nq + 2 * d * nkv + nq * d


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def ffn_shared_params(c: Dict[str, Any]) -> int:
    """What every row reads of a layer's expert block whatever the routing:
    the router (every published column) and the shared expert with its
    gate."""
    d = int(c["hidden_size"])
    return (d * int(c["share"]["num_experts"])
            + 3 * d * int(c["shared_expert_intermediate_size"]) + d)


def n_attention_layers(c: Dict[str, Any]) -> int:
    period = int(c["full_attention_interval"])
    return sum((i + 1) % period == 0
               for i in range(int(c["num_hidden_layers"])))


def outside_expert_params(c: Dict[str, Any]) -> int:
    """Every parameter of the layers outside the routed experts."""
    n_layers, n_attn = int(c["num_hidden_layers"]), n_attention_layers(c)
    return ((n_layers - n_attn) * gdn_params(c)
            + n_attn * attention_params(c) + n_layers * ffn_shared_params(c))


def head_params(c: Dict[str, Any]) -> int:
    """The untied output head over the slice of the vocabulary held here."""
    return int(c["vocab_size"]) * int(c["hidden_size"])


def model_params(c: Dict[str, Any]) -> int:
    """Everything this chip holds: the layers outside the experts, the held
    experts of every layer, the embedding and the head."""
    return (outside_expert_params(c)
            + int(c["num_hidden_layers"]) * int(c["num_experts"])
            * expert_params(c) + 2 * head_params(c))


def state_bytes_per_lane(c: Dict[str, Any]) -> int:
    """Recurrent state a lane holds, all Gated DeltaNet layers: a float32
    ``d_k x d_v`` matrix a value head and the convolution's tail in bf16."""
    nk, nv, hv, taps = _gdn_widths(c)
    n_gdn = int(c["num_hidden_layers"]) - n_attention_layers(c)
    return n_gdn * (hv * int(c["linear_key_head_dim"])
                    * int(c["linear_value_head_dim"]) * STATE_BYTES
                    + (taps - 1) * (2 * nk + nv) * BYTES)


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """K and V rows a token leaves in the attention layers' pages."""
    return (n_attention_layers(c) * 2 * int(c["num_key_value_heads"])
            * int(c["head_dim"]) * BYTES)


def _lanes_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """The live lanes' state read and written, and their K/V rows read."""
    return lanes * (2.0 * state_bytes_per_lane(c)
                    + context * kv_bytes_per_token(c))


def decode_step_bytes(c: Dict[str, Any], lanes: float, experts_hit: float,
                      context: float) -> float:
    """Bytes one decode step has to move: the weights outside the experts
    once, the held experts that a row chose (``experts_hit`` a layer, the
    mean), the head, and the live lanes' state (read and written) and K/V
    rows (``context`` tokens a lane).  A LOWER bound of a step's traffic:
    embedding rows, norm scales and activations are left out, so a share of
    the roofline computed from it cannot pass 100 %."""
    weights = (outside_expert_params(c) + int(c["num_hidden_layers"])
               * experts_hit * expert_params(c) + head_params(c))
    return BYTES * weights + _lanes_bytes(c, lanes, context)


def round_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """Bytes one mixed round has to move: every held weight once (256
    prompt tokens at top-10 reach every held expert), the head, and the
    live lanes' state and K/V rows.  A lower bound, as above."""
    weights = model_params(c) - head_params(c)          # no embedding
    return BYTES * weights + _lanes_bytes(c, lanes, context)


def chunk_delta_rule_cost(rows: int, heads: int, segments: int = 1,
                          d_k: int = 128, d_v: int = 128) -> Dict[str, float]:
    """``{"flops", "bytes"}`` the chunk form of the gated delta rule of ONE
    layer needs for ``rows`` token rows in ``segments`` segments on
    ``heads`` value heads.  A pass of ``CHUNK`` rows a head: ``K K^T`` and
    ``Q K^T`` (2 C^2 d_k each), the triangular inverse by doubling (ten C^3
    products), ``T (beta V)`` and ``(Q K^T) v'`` (2 C^2 d_v each), ``T (beta
    K e^G)`` (2 C^2 d_k), and three products with the state (2 C d_k d_v
    each).  Bytes: q, k, v in and o out in float32, and each segment's
    state read and written once."""
    c = CHUNK
    passes = -(-rows // c) * heads
    flops = 2.0 * passes * (3 * c * c * d_k + 2 * c * c * d_v + 10 * c ** 3
                            + 3 * c * d_k * d_v)
    nbytes = STATE_BYTES * (rows * heads * (2.0 * d_k + 2.0 * d_v)
                            + 2.0 * segments * heads * d_k * d_v)
    return {"flops": flops, "bytes": nbytes}
