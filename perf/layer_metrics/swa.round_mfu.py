"""Share of the bf16 peak a mixed round of kind ``mellum`` reaches: the
operations of the rows the window's rounds RAN (``perf/rooflines/mellum.py``
``round_flops``: every row that held a token through the projections and the
router of every layer; the (row, expert) assignments; the (query row, key)
pairs of the attentions, the full layers' ``round_attn_pairs`` and the
window layers' ``round_window_pairs``, a row at context ``n`` counted at
``min(n, window)`` there; a head row a lane with a segment) over the
device's bf16 peak (``perf/peaks.json``), over the MEAN device time of a
``jit_paged_mixed_step`` execution in the traced slice: the construction of
``cca.round_mfu``, with ``scmoe.round_mfu``'s ``round_work`` (the window's
mean round, ``debug_state()["dispatch"]`` and ``["moe"]``).  A share of the
whole step.  A round also moves ``round_bytes``: :func:`bounds` gives both
floors, so a reader sees which one a round is under.  None on a program (or a
model) without window layers."""

import os

from harness.counters import delta
from harness.spec import PERF_DIR, load_json

PROGRAM = "jit_paged_mixed_step"


def bounds(ctx):
    """``{"flops_s", "bytes_s"}``: the seconds the mean round's operations
    take at the bf16 peak and its bytes at the HBM bandwidth."""
    import jax
    cell = ctx["cell"]
    work = cell.module("layer_metrics", "scmoe.round_mfu").round_work(ctx)
    peaks = load_json(os.path.join(PERF_DIR, "peaks.json"))["devices"].get(
        jax.devices()[0].device_kind)
    at = cell.module("layer_metrics", "gdn.decode_roofline"
                     ).lanes_and_context(ctx, "round", "kinds", "mixed")
    rounds = delta(ctx, "kinds", "mixed")
    window_pairs = delta(ctx, "round_window_pairs")
    window_keys = delta(ctx, "lane_work", "round", "window_keys")
    passes = delta(ctx, "lane_work", "round", "passes")
    if (work is None or at is None or not peaks or window_pairs is None
            or window_keys is None or not rounds or not passes):
        return None
    tokens, expert_rows, pairs, lanes = work
    roofline = cell.module("rooflines", cell.config["kind"])
    return {"flops_s": roofline.round_flops(
                cell.config, tokens, expert_rows, pairs,
                window_pairs / rounds, lanes) / peaks["bf16_flops_per_s"],
            "bytes_s": roofline.round_bytes(
                cell.config, at[0], at[1], window_keys / passes)
            / peaks["hbm_bytes_per_s"]}


def read(ctx):
    trace = ctx["trace"]
    if not trace or delta(ctx, "round_window_pairs") is None:
        return None
    times = trace["modules"].get(PROGRAM, {}).get("durations_s")
    floors = bounds(ctx) if times else None
    if not floors:
        return None
    mean = sum(times) / len(times)
    if ctx.get("say"):
        ctx["say"](f"swa.round_mfu: a round's operations are "
                   f"{1e3 * floors['flops_s']:.2f} ms at the bf16 peak and "
                   f"its bytes {1e3 * floors['bytes_s']:.2f} ms at the HBM "
                   f"bandwidth; the mean round took {1e3 * mean:.2f} ms")
    return 100.0 * floors["flops_s"] / mean
