"""Operations and bytes of what kind ``evabyte`` adds, from shapes alone.

Kept with the benchmark so that every PR computes a roofline share the same
way.  ``decode_step_bytes`` feeds ``eva.decode_roofline``; ``summary_cost`` is
the new Pallas kernel's (``eva_chunk_summary``) operations and bytes and
feeds ``eva.summary_roofline``.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2           # bf16, the precision the configuration states


def _widths(c: Dict[str, Any]):
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    return d, h, int(c["num_key_value_heads"]), d // h


def layer_params(c: Dict[str, Any]) -> int:
    """One layer: the four attention projections, the SwiGLU's three, the
    two norms and the two scorers (``mu``, ``phi``: a vector a head)."""
    d, h, hkv, hd = _widths(c)
    attention = d * h * hd + 2 * d * hkv * hd + h * hd * d
    return (attention + 3 * d * int(c["intermediate_size"]) + 2 * d
            + 2 * h * hd)


def head_params(c: Dict[str, Any]) -> int:
    """The output head at its published size: ``num_pred_heads x
    vocab_size`` rows."""
    return (int(c["num_pred_heads"]) * int(c["vocab_size"])
            * int(c["hidden_size"]))


def model_params(c: Dict[str, Any]) -> int:
    """Everything the chip holds: the layers, the embedding, the final
    norm and every prediction head."""
    d = int(c["hidden_size"])
    return (int(c["num_hidden_layers"]) * layer_params(c)
            + int(c["vocab_size"]) * d + d + head_params(c))


def kv_bytes_per_row(c: Dict[str, Any]) -> int:
    """K and V a ROW of a lane's table holds, all layers (a position inside
    the lane's last window, or the summary of a chunk before it)."""
    _, _, hkv, hd = _widths(c)
    return int(c["num_hidden_layers"]) * 2 * hkv * hd * BYTES


def rows_of(c: Dict[str, Any], positions: int) -> int:
    """The rows a lane holds after ``positions`` positions: its finished
    windows as ``window / chunk`` summaries each, the last window whole."""
    w, per = int(c["window_size"]), int(c["window_size"]) // int(
        c["chunk_size"])
    done = max(positions - 1, 0) // w
    return done * per + positions - done * w


def decode_step_bytes(c: Dict[str, Any], lanes: float, rows: float) -> float:
    """Bytes one decode step has to move: the layers' weights once, the one
    prediction head that is read, and the rows (summaries and the window's
    own: ``rows`` a lane) of the lanes that ran.  A LOWER bound of a step's
    traffic: embedding rows, the final norm and activations are left out, so
    a share of the roofline computed from it cannot pass 100 %."""
    weights = (int(c["num_hidden_layers"]) * layer_params(c)
               + int(c["vocab_size"]) * int(c["hidden_size"]))
    return BYTES * weights + lanes * rows * kv_bytes_per_row(c)


def summary_cost(c: Dict[str, Any], windows: int = 1) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of compacting ``windows`` finished windows of
    one lane, every layer: a window's rows read once, one row in
    ``chunk_size`` written; a row's key scored twice (``mu``, ``phi``: 2 x 2
    d operations a head) and a key and a value weighed once each."""
    _, _, hkv, hd = _widths(c)
    w, chunk = int(c["window_size"]), int(c["chunk_size"])
    rows = windows * w
    nbytes = (rows + rows // chunk) * kv_bytes_per_row(c)
    flops = (int(c["num_hidden_layers"]) * rows * hkv * (2 * 2 * hd
                                                         + 2 * 2 * hd))
    return {"flops": float(flops), "bytes": float(nbytes)}
