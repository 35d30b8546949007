"""Share of the HBM roofline a decode step of kind ``zaya`` reaches: the bytes
a step must move (``perf/rooflines/zaya.py`` ``decode_step_bytes``: the
weights outside the experts and the tied table once, the experts the
window's decode steps hit a layer (``moe.experts_hit_per_step``), the K/V
rows of the lanes that RAN a step and their three tails read and written)
over the device's HBM bandwidth (``perf/peaks.json``), over the MEAN device
time of a decode step in the ``jit_paged_decode_block_k<K>`` programs of the
traced slice: the construction of ``mhc.decode_roofline``, with
``gdn.decode_roofline``'s ``lanes_and_context`` and ``hbm_bytes_per_s``
(lanes and context are the window's, from what the scheduler dispatched:
``lane_work["decode"]``).  Weights are counted once and activations not at
all, so the bytes are a lower bound of a step's traffic.  None on a program
(or a model) without CCA, or where the traced slice holds no decode block."""

import re

PROGRAM = re.compile(r"^jit_paged_decode_block_k(\d+)$")


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    if not trace or not ctx["counters_after"].get("cca"):
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
