"""Share of the HBM roofline a decode step of kind ``nemotron_h`` reaches: the
bytes a step must move (``perf/rooflines/nemotron_h.py`` ``decode_step_bytes``:
the weights outside the experts and the head's slice once, the held experts
the window's decode steps hit an expert layer (``moe.experts_hit_per_step``),
the shared experts, and for the lanes that RAN a step their Mamba-2 state read
and written and the K/V rows at or before their row) over the device's HBM
bandwidth (``perf/peaks.json``), over the MEAN device time of a decode step in
the traced slice: the construction of ``gdn.decode_roofline``, whose helpers
it takes.  The lanes and their context are the window's, from what the
scheduler dispatched (``debug_state()["dispatch"]["lane_work"]["decode"]``),
not from the pool's gauges.  Weights are counted once at their PUBLISHED
widths (the served experts' padding is not) and activations not at all, so
the bytes are a lower bound of a step's traffic and the share cannot pass
100 %.  None on a program without a Mamba-2 state."""

import re

PROGRAM = re.compile(r"^jit_paged_decode_block_k(\d+)$")


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    state = ctx["counters_after"].get("state") or {}
    if not trace or state.get("kind") != "mamba2":
        return None
    gdn = cell.module("layer_metrics", "gdn.decode_roofline")
    hit = cell.module("layer_metrics", "moe.experts_hit_per_step").read(ctx)
    at = gdn.lanes_and_context(ctx, "decode", "decode_block_steps")
    bandwidth = gdn.hbm_bytes_per_s()
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
