"""Operations and bytes of what kind ``jamba`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a roofline share the same
way.  ``decode_step_bytes`` feeds ``ssm.decode_roofline``;
``selective_scan_cost`` is the new Pallas kernel's (``selective_scan``)
operations and bytes, whose share the benchmark cannot read yet
(``reduce_trace`` keeps ten operations; PERF.md section 7) and PERF.md
reports from a full trace.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states
STATE_BYTES = 4     # the SSM state is float32


def _widths(c: Dict[str, Any]):
    d = int(c["hidden_size"])
    return (d, int(c["mamba_expand"]) * d, int(c["mamba_d_state"]),
            int(c["mamba_d_conv"]), int(c["mamba_dt_rank"]))


def mamba_params(c: Dict[str, Any]) -> int:
    """Parameters of one Mamba mixer (norm scales left out)."""
    d, di, n, k, r = _widths(c)
    return (d * 2 * di + di * k + di + di * (r + 2 * n) + r * di + di
            + di * n + di + di * d)


def attention_params(c: Dict[str, Any]) -> int:
    """Parameters of one attention mixer: q, k, v, o without bias."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    kv = int(c["num_key_value_heads"]) * (d // h)
    return d * d + 2 * d * kv + d * d


def n_attention_layers(c: Dict[str, Any]) -> int:
    period, offset = int(c["attn_layer_period"]), int(c["attn_layer_offset"])
    return sum(i % period == offset
               for i in range(int(c["num_hidden_layers"])))


def model_params(c: Dict[str, Any]) -> int:
    """All parameters a decode step reads: every mixer, every layer's MLP,
    the tied embedding once (as the output head)."""
    n_layers, n_attn = int(c["num_hidden_layers"]), n_attention_layers(c)
    mlp = 3 * int(c["hidden_size"]) * int(c["intermediate_size"])
    return ((n_layers - n_attn) * mamba_params(c)
            + n_attn * attention_params(c) + n_layers * mlp
            + int(c["vocab_size"]) * int(c["hidden_size"]))


def state_bytes_per_lane(c: Dict[str, Any]) -> int:
    """Recurrent state a lane holds, all Mamba layers: the float32 SSM state
    and the ``d_conv - 1`` inputs of the convolution's tail in bf16."""
    _, di, n, k, _ = _widths(c)
    n_mamba = int(c["num_hidden_layers"]) - n_attention_layers(c)
    return n_mamba * (di * n * STATE_BYTES + (k - 1) * di * BYTES)


def decode_step_bytes(c: Dict[str, Any], lanes: float) -> float:
    """Bytes one decode step has to move: the weights once, whatever the
    batch, and the recurrent state of ``lanes`` live lanes read and written.
    A LOWER bound of a step's traffic: the attention layers' pages, the
    embedding rows, norm scales and activations are left out, so a share of
    the roofline computed from it cannot pass 100 %."""
    return BYTES * model_params(c) + 2.0 * lanes * state_bytes_per_lane(c)


def selective_scan_cost(c: Dict[str, Any], rows: int,
                        segments: int) -> Dict[str, float]:
    """``{"exps", "flops", "bytes"}`` the segmented scan of ONE layer needs
    for a round of ``rows`` token rows in ``segments`` segments: an ``exp``
    and a multiply-add into the state and a multiply-add into ``y`` for
    every (row, state, channel), ``dt * A`` and ``dt * u`` beside them;
    bytes are ``u``, ``dt`` in and ``y`` out in float32, ``B`` and ``C``,
    and each segment's state read and written once."""
    _, di, n, _, _ = _widths(c)
    exps = float(rows) * n * di
    flops = 6.0 * exps + 3.0 * rows * di
    nbytes = STATE_BYTES * (3.0 * rows * di + 2.0 * rows * n
                            + 2.0 * segments * n * di)
    return {"exps": exps, "flops": flops, "bytes": nbytes}
