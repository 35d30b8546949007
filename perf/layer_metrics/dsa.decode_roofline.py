"""Share of the HBM roofline a decode step of a model with a learned indexer
reaches: the bytes a step must move (``perf/rooflines/<kind>.py``
``decode_step_bytes``: the weights touched at the window's mean experts hit,
and for the traced slice's mean active lanes at the window's mean decode
context (``keys_scored.decode / query_rows.decode``) the index keys and the K
and V rows of the selected keys) over the device's HBM bandwidth
(``perf/peaks.json``), over the MEAN device time of a decode step in the
traced slice (the summed durations of the ``jit_paged_decode_block_k<K>``
executions over their summed K): the construction of
``step.decode_weight_roofline``.  A lower bound of bytes over the time the
steps took, so it cannot pass 100 %."""

import os
import re

from harness.spec import PERF_DIR, load_json

PROGRAM = re.compile(r"^jit_paged_decode_block_k(\d+)$")


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    a = ctx["counters_before"].get("sparse")
    b = ctx["counters_after"].get("sparse")
    if not trace or not a or not b:
        return None
    rows = b["query_rows"]["decode"] - a["query_rows"]["decode"]
    hit = cell.module("layer_metrics", "moe.experts_hit_per_step").read(ctx)
    lanes = cell.module("layer_metrics", "sched.active_lanes_mean").read(ctx)
    if not rows or hit is None or lanes is None:
        return None
    mean_ctx = (b["keys_scored"]["decode"] - a["keys_scored"]["decode"]) / rows
    import jax
    peaks = load_json(os.path.join(PERF_DIR, "peaks.json"))["devices"]
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
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
        cell.config, lanes, mean_ctx, hit)
    return 100.0 * (nbytes / peaks[kind]["hbm_bytes_per_s"]) / (
        total_s / steps)
