"""Operations and bytes of what kind ``glm4_moe_lite`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a roofline share the same
way.  ``decode_step_bytes`` feeds ``step.decode_weight_roofline``;
``latent_attention_cost`` is the new Pallas kernel's
(``ragged_latent_attention``) operations and bytes, whose share of the
roofline the benchmark cannot read yet (``reduce_trace`` keeps ten
operations; PERF.md section 7) and PERF.md reports from a full trace.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2       # bf16, the precision the configuration states


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one layer's attention (norm scales left out)."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    ql, kl = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    nope, rope, v = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                     int(c["v_head_dim"]))
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def expert_params(c: Dict[str, Any]) -> int:
    """Parameters of one routed expert (gate, up, down)."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def decode_step_bytes(c: Dict[str, Any], experts_hit_per_layer: float) -> float:
    """Weight bytes one decode step has to read, whatever the batch: every
    layer's attention, the dense layers' FFN, and in an expert layer the
    router, the shared experts and the ``experts_hit_per_layer`` routed
    experts that at least one row chose (the mean over steps and expert
    layers), then the output head.  A LOWER bound of a step's traffic: the
    latent pages, the embedding rows, norm scales and activations are left
    out, so a share of the roofline computed from it cannot pass 100 %."""
    d = int(c["hidden_size"])
    n_layers = int(c["num_hidden_layers"])
    n_dense = int(c["first_k_dense_replace"])
    n_moe = n_layers - n_dense
    per_moe = (d * int(c["n_routed_experts"])
               + int(c["n_shared_experts"]) * expert_params(c)
               + experts_hit_per_layer * expert_params(c))
    params = (n_layers * attention_params(c)
              + n_dense * 3 * d * int(c["intermediate_size"])
              + n_moe * per_moe + d * int(c["vocab_size"]))
    return BYTES * params


def latent_attention_cost(c: Dict[str, Any], q_lens, kv_lens,
                          page_size: int, row: int) -> Dict[str, float]:
    """``{"flops", "bytes"}`` the absorbed attention of ONE layer needs for
    lanes with ``q_lens`` query tokens against ``kv_lens`` cached positions
    (the segment's own included): scores over the ``kv_lora_rank +
    qk_rope`` latent width and values over ``kv_lora_rank`` for every head,
    causal within the segment; bytes are the lanes' live pages read once
    (``row`` stored values a position) plus the queries in and the latent
    sums out."""
    h = int(c["num_attention_heads"])
    kl, rope = int(c["kv_lora_rank"]), int(c["qk_rope_head_dim"])
    flops = nbytes = 0.0
    for q, kv in zip(q_lens, kv_lens):
        if not q:
            continue
        # query j of the segment sees kv - q + j + 1 positions
        seen = q * (kv - q) + q * (q + 1) / 2
        flops += 2.0 * h * seen * ((kl + rope) + kl)
        pages = -(-kv // page_size)
        nbytes += BYTES * (pages * page_size * row + q * h * (kl + rope)
                           + q * h * kl)
    return {"flops": flops, "bytes": nbytes}
