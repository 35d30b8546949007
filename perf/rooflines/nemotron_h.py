"""Operations and bytes of what kind ``nemotron_h`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a share the same way.
``decode_step_bytes`` feeds ``ssd.decode_roofline``; ``round_flops`` feeds
``ssd.round_mfu`` and ``round_bytes`` stands beside it, so that a reader sees
which bound a round is under; ``ssd_chunk_cost`` is what the chunked form of
the Mamba-2 recurrence (``tpulab.ops.ssd.chunk_ssd``) computes and moves.
``n_routed_experts`` of the configuration is what this chip HOLDS; the
router's width is ``share.n_routed_experts``.  Every width is the PUBLISHED
one: the served experts are padded from 1,856 to 1,920 columns (whole lanes),
and what the padding costs shows as lost share.  A layer is ONE sublayer by
its letter of ``hybrid_override_pattern``.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states
STATE_BYTES = 4     # the Mamba-2 state is float32


def pattern(c: Dict[str, Any]) -> str:
    """The letters of the layers served: the published pattern's first
    ``num_hidden_layers``."""
    return str(c["hybrid_override_pattern"])[:int(c["num_hidden_layers"])]


def _mamba_widths(c: Dict[str, Any]):
    heads, p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    gn = int(c["n_groups"]) * int(c["ssm_state_size"])
    return heads, p, heads * p, heads * p + 2 * gn, int(c["conv_kernel"])


def mamba_params(c: Dict[str, Any]) -> int:
    """Parameters of one Mamba-2 mixer (``A_log``, ``D``, ``dt_bias`` and
    the norm's weight left out): ``in_proj``, the convolution with its
    bias, ``out_proj``."""
    d = int(c["hidden_size"])
    heads, _p, din, conv, taps = _mamba_widths(c)
    return d * (din + conv + heads) + conv * (taps + 1) + din * d


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one attention mixer: q, k, v, o, without bias."""
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    nq = int(c["num_attention_heads"]) * hd
    return 2 * d * nq + 2 * d * int(c["num_key_value_heads"]) * hd


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert at its published width: up and down."""
    return 2 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def ffn_shared_params(c: Dict[str, Any]) -> int:
    """What every row reads of an expert layer whatever the routing: the
    router (every published column) and the shared expert."""
    d = int(c["hidden_size"])
    return (d * int(c["share"]["n_routed_experts"])
            + 2 * d * int(c["moe_shared_expert_intermediate_size"])
            * int(c.get("n_shared_experts", 1)))


def outside_expert_params(c: Dict[str, Any]) -> int:
    """Every parameter of the layers outside the routed experts."""
    letters = pattern(c)
    return (letters.count("M") * mamba_params(c)
            + letters.count("*") * attention_params(c)
            + letters.count("E") * ffn_shared_params(c))


def head_params(c: Dict[str, Any]) -> int:
    """The untied output head over the slice of the vocabulary held here."""
    return int(c["vocab_size"]) * int(c["hidden_size"])


def model_params(c: Dict[str, Any]) -> int:
    """Everything this chip holds at the published widths: the layers
    outside the experts, the held experts of every expert layer, the
    embedding and the head."""
    return (outside_expert_params(c) + pattern(c).count("E")
            * int(c["n_routed_experts"]) * expert_params(c)
            + 2 * head_params(c))


def state_bytes_per_lane(c: Dict[str, Any]) -> int:
    """Recurrent state a lane holds, all Mamba-2 layers: a float32 ``head_dim
    x state`` matrix a head and the convolution's tail in bf16."""
    heads, p, _din, conv, taps = _mamba_widths(c)
    return pattern(c).count("M") * (
        heads * p * int(c["ssm_state_size"]) * STATE_BYTES
        + (taps - 1) * conv * BYTES)


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """K and V rows a token leaves in the attention layers' pages."""
    return (pattern(c).count("*") * 2 * int(c["num_key_value_heads"])
            * int(c["head_dim"]) * BYTES)


def _lanes_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """The live lanes' state read and written, and their K/V rows read."""
    return lanes * (2.0 * state_bytes_per_lane(c)
                    + context * kv_bytes_per_token(c))


def decode_step_bytes(c: Dict[str, Any], lanes: float, experts_hit: float,
                      context: float) -> float:
    """Bytes one decode step has to move: the weights outside the experts
    once, the held experts that a row chose (``experts_hit`` an expert
    layer, the mean), the head's slice, and the state (read and written)
    and K/V rows (``context`` tokens a lane) of the ``lanes`` that ran the
    step.  A LOWER bound of a step's traffic: embedding rows, norm scales,
    activations and the experts' padding are left out, so a share of the
    roofline computed from it cannot pass 100 %."""
    weights = (outside_expert_params(c) + pattern(c).count("E")
               * experts_hit * expert_params(c) + head_params(c))
    return BYTES * weights + _lanes_bytes(c, lanes, context)


def round_bytes(c: Dict[str, Any], lanes: float, context: float) -> float:
    """Bytes one mixed round has to move: every held weight once (512
    prompt tokens at top-6 of 128 reach every held expert), the head, and
    the state and K/V rows of the lanes that had a segment.  A lower bound,
    as above."""
    weights = model_params(c) - head_params(c)          # no embedding
    return BYTES * weights + _lanes_bytes(c, lanes, context)


def ssm_row_flops(c: Dict[str, Any]) -> int:
    """Operations ONE row costs ONE Mamba-2 layer's recurrence at least:
    the state's update (``(dt x) (x) B`` added to the decayed state) and its
    readout (``S C``), two a value of the ``heads x head_dim x state``
    state each.  The chunked form computes more (:func:`ssd_chunk_cost`)."""
    heads, p, _din, _conv, _taps = _mamba_widths(c)
    return 4 * heads * p * int(c["ssm_state_size"])


def attention_pair_flops(c: Dict[str, Any]) -> int:
    """Operations ONE (query row, key) pair costs ONE attention layer:
    every query head's score and its weighted sum over ``head_dim``."""
    return 4 * int(c["num_attention_heads"]) * int(c["head_dim"])


def round_flops(c: Dict[str, Any], tokens: float, expert_rows: float,
                pairs: float, head_rows: float) -> float:
    """Operations the rows of one mixed round cost: ``tokens`` rows through
    every projection, the router and the shared expert of every layer (two
    a parameter a row) and through the recurrence of every Mamba-2 layer
    (:func:`ssm_row_flops`), ``expert_rows`` (row, expert) assignments that
    landed on held experts (all expert layers together, at the published
    width), ``pairs`` (query row, key) pairs through each attention layer,
    ``head_rows`` rows through the head.  Only rows that held a token are
    counted (a round also computes its padding), so a share of the peak
    computed from it cannot pass 100 %."""
    letters = pattern(c)
    return (2.0 * tokens * outside_expert_params(c)
            + tokens * letters.count("M") * ssm_row_flops(c)
            + 2.0 * expert_rows * expert_params(c)
            + pairs * letters.count("*") * attention_pair_flops(c)
            + 2.0 * head_rows * head_params(c))


def ssd_chunk_cost(rows: int, c: Dict[str, Any], segments: int = 1,
                   ) -> Dict[str, float]:
    """``{"flops", "bytes"}`` the chunked form of ONE Mamba-2 layer's
    recurrence needs for ``rows`` token rows in ``segments`` segments, in
    whole chunks of ``chunk_size`` (Q): a chunk a group ``C B^T`` (2 Q^2
    N), a chunk a head the masked scores through ``dt x`` (2 Q^2 P), the
    incoming state's readout and the state's update (2 Q P N each).  Bytes:
    x, B, C and dt in and y out in float32, and each segment's state read
    and written once."""
    heads, p, _din, _conv, _taps = _mamba_widths(c)
    groups, n, q = (int(c["n_groups"]), int(c["ssm_state_size"]),
                    int(c["chunk_size"]))
    chunks = -(-rows // q)
    flops = 2.0 * chunks * (groups * q * q * n
                            + heads * (q * q * p + 2 * q * p * n))
    nbytes = STATE_BYTES * (rows * (2.0 * heads * p + 2.0 * groups * n
                                    + heads)
                            + 2.0 * segments * heads * p * n)
    return {"flops": flops, "bytes": nbytes}
