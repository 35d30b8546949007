"""Share of the bf16 peak a mixed round of kind ``longcat_flash`` reaches: the
operations of the rows the window's rounds RAN (``perf/rooflines/
longcat_flash.py`` ``round_flops``: every row that held a token through the
projections, both dense FFNs and the router of every layer; the (row,
expert) assignments that landed on held experts; the (query row, key) pairs
of the latent attentions; a head row a lane with a segment) over the
device's bf16 peak (``perf/peaks.json``), over the MEAN device time of a
``jit_paged_mixed_step`` execution in the traced slice.  The rows are the
window's, a round: ``mixed_tokens`` (the rows of ``mixed_rows`` that held a
token), ``lane_work["round"]["passes"]`` and ``round_attn_pairs`` over
``kinds["mixed"]`` (``debug_state()["dispatch"]``); the assignments on held
experts are ``moe.assignments_here`` in the rounds' share of all rows the
window routed (rounds and decode steps route alike).  A round also moves
``round_bytes``: :func:`bounds` gives both floors, so a reader sees which
one a round is under."""

import os

from harness.counters import delta
from harness.spec import PERF_DIR, load_json

PROGRAM = "jit_paged_mixed_step"


def round_work(ctx):
    """``(tokens, expert rows, pairs, lanes)`` of the window's mean round, or
    None on a program without the counters."""
    rounds = delta(ctx, "kinds", "mixed")
    got = [delta(ctx, *path) for path in (
        ("mixed_tokens",), ("round_attn_pairs",),
        ("lane_work", "round", "passes"), ("lane_work", "decode", "rows"))]
    a = ctx["counters_before"].get("moe")
    b = ctx["counters_after"].get("moe")
    if not rounds or None in got or not a or not b or (
            "assignments_here" not in b):
        return None
    tokens, pairs, lanes, decode_rows = got
    here = sum(b["assignments_here"]) - sum(a["assignments_here"])
    return (tokens / rounds,
            here * tokens / max(tokens + decode_rows, 1) / rounds,
            pairs / rounds, lanes / rounds)


def bounds(ctx):
    """``{"flops_s", "bytes_s"}``: the seconds the mean round's operations
    take at the bf16 peak and its bytes at the HBM bandwidth."""
    import jax
    cell, work = ctx["cell"], round_work(ctx)
    peaks = load_json(os.path.join(PERF_DIR, "peaks.json"))["devices"].get(
        jax.devices()[0].device_kind)
    gdn = cell.module("layer_metrics", "gdn.decode_roofline")
    at = gdn.lanes_and_context(ctx, "round", "kinds", "mixed")
    if work is None or at is None or not peaks:
        return None
    roofline = cell.module("rooflines", cell.config["kind"])
    return {"flops_s": roofline.round_flops(cell.config, *work)
            / peaks["bf16_flops_per_s"],
            "bytes_s": roofline.round_bytes(cell.config, *at)
            / peaks["hbm_bytes_per_s"]}


def read(ctx):
    trace = ctx["trace"]
    moe = ctx["counters_after"].get("moe") or {}
    if not trace or not moe.get("zero_columns"):
        return None
    times = trace["modules"].get(PROGRAM, {}).get("durations_s")
    floors = bounds(ctx) if times else None
    if not floors:
        return None
    mean = sum(times) / len(times)
    if ctx.get("say"):
        ctx["say"](f"scmoe.round_mfu: a round's operations are "
                   f"{1e3 * floors['flops_s']:.2f} ms at the bf16 peak and "
                   f"its bytes {1e3 * floors['bytes_s']:.2f} ms at the HBM "
                   f"bandwidth; the mean round took {1e3 * mean:.2f} ms")
    return 100.0 * floors["flops_s"] / mean
