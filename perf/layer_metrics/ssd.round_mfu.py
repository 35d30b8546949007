"""Share of the bf16 peak a mixed round of kind ``nemotron_h`` reaches: the
operations of the rows the window's rounds RAN (``perf/rooflines/
nemotron_h.py`` ``round_flops``: every row that held a token through the
projections, the router and the shared expert of every layer and through the
recurrence of every Mamba-2 layer; the (row, expert) assignments that landed
on held experts, at the published width; the (query row, key) pairs of the
attention layers; a head row a lane with a segment) over the device's bf16
peak (``perf/peaks.json``), over the MEAN device time of a
``jit_paged_mixed_step`` execution in the traced slice: the construction of
``scmoe.round_mfu``, whose ``round_work`` it takes.  A round also moves
``round_bytes``: :func:`bounds` gives both floors, so a reader sees which one
a round is under.  Only rows that held a token are counted, so the share
cannot pass 100 %.  None on a program without a Mamba-2 state."""

import os

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
    at = cell.module("layer_metrics", "gdn.decode_roofline").lanes_and_context(
        ctx, "round", "kinds", "mixed")
    if work is None or at is None or not peaks:
        return None
    roofline = cell.module("rooflines", cell.config["kind"])
    return {"flops_s": roofline.round_flops(cell.config, *work)
            / peaks["bf16_flops_per_s"],
            "bytes_s": roofline.round_bytes(cell.config, *at)
            / peaks["hbm_bytes_per_s"]}


def read(ctx):
    trace = ctx["trace"]
    state = ctx["counters_after"].get("state") or {}
    if not trace or state.get("kind") != "mamba2":
        return None
    times = trace["modules"].get(PROGRAM, {}).get("durations_s")
    floors = bounds(ctx) if times else None
    if not floors:
        return None
    mean = sum(times) / len(times)
    if ctx.get("say"):
        ctx["say"](f"ssd.round_mfu: a round's operations are "
                   f"{1e3 * floors['flops_s']:.2f} ms at the bf16 peak and "
                   f"its bytes {1e3 * floors['bytes_s']:.2f} ms at the HBM "
                   f"bandwidth; the mean round took {1e3 * mean:.2f} ms")
    return 100.0 * floors["flops_s"] / mean
