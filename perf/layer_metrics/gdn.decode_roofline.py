"""Share of the HBM roofline a decode step of kind ``qwen3_next`` reaches: the
bytes a step must move (``perf/rooflines/qwen3_next.py`` ``decode_step_bytes``:
the weights outside the experts and the head once, the held experts the
window's decode steps hit a layer (``moe.experts_hit_per_step``), and for
the lanes that RAN a step their state read and written and the K/V rows at
or before their row) over the device's HBM bandwidth (``perf/peaks.json``),
over the MEAN device time of a decode step in the traced slice: the
construction of ``ssm.decode_roofline``.  The lanes and their context are
the window's, from what the scheduler dispatched (``debug_state()
["dispatch"]["lane_work"]["decode"]``: ``passes`` over ``decode_block_steps``
lanes a step, ``keys`` over ``passes`` keys a lane), not from the pool's
gauges: a lane that prefills holds pages and runs no decode step.  Weights
are counted once and activations not at all, so the bytes are a lower
bound of a step's traffic."""

import os
import re

from harness.spec import PERF_DIR, load_json

PROGRAM = re.compile(r"^jit_paged_decode_block_k(\d+)$")


def lanes_and_context(ctx, kind, *dispatches):
    """``(lanes a dispatch, keys a lane)`` of the window's dispatches of
    ``kind`` ("decode" | "round"), from the scheduler's ``lane_work`` and
    the integer of ``debug_state()["dispatch"]`` at ``dispatches`` that
    counts them; None on a program without the counters."""
    a = ctx["counters_before"].get("dispatch") or {}
    b = ctx["counters_after"].get("dispatch") or {}
    if "lane_work" not in a or "lane_work" not in b:
        return None
    work = {name: b["lane_work"][kind][name] - a["lane_work"][kind][name]
            for name in ("passes", "keys")}
    for key in dispatches:
        a, b = a[key], b[key]
    if b - a <= 0 or not work["passes"]:
        return None
    return work["passes"] / (b - a), work["keys"] / work["passes"]


def hbm_bytes_per_s():
    """The attached device's HBM bandwidth (``perf/peaks.json``), or None
    for a device the table does not know."""
    import jax
    peaks = load_json(os.path.join(PERF_DIR, "peaks.json"))["devices"]
    return peaks.get(jax.devices()[0].device_kind, {}).get("hbm_bytes_per_s")


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    state = ctx["counters_after"].get("state") or {}
    if not trace or state.get("kind") != "gdn":
        return None
    hit = cell.module("layer_metrics", "moe.experts_hit_per_step").read(ctx)
    at = lanes_and_context(ctx, "decode", "decode_block_steps")
    bandwidth = hbm_bytes_per_s()
    if hit is None or at is None or bandwidth is None:
        return None
    total_s = steps = 0
    for name, rec in trace["modules"].items():
        m = PROGRAM.match(name)
        if m:
            total_s += sum(rec["durations_s"])
            steps += int(m.group(1)) * len(rec["durations_s"])
    if not steps:
        return None
    nbytes = cell.module("rooflines", cell.config["kind"]).decode_step_bytes(
        cell.config, at[0], hit, at[1])
    return 100.0 * (nbytes / bandwidth) / (total_s / steps)
