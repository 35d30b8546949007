"""Share of the HBM roofline a decode step of a model with recurrent state
reaches: the bytes a step must move (``perf/rooflines/<kind>.py``
``decode_step_bytes``: the weights once, the state of the live lanes read and
written, at the traced slice's mean active lanes) over the device's HBM
bandwidth (``perf/peaks.json``), over the MEAN device time of a decode step in
the traced slice (the summed durations of the
``jit_paged_decode_block_k<K>`` executions over their summed K), the
construction of ``step.decode_weight_roofline``.  A lower bound of bytes over
the time the steps took, so it cannot pass 100 %."""

import os
import re

from harness.spec import PERF_DIR, load_json

PROGRAM = re.compile(r"^jit_paged_decode_block_k(\d+)$")


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    if not trace or not ctx["counters_after"].get("state"):
        return None
    lanes = cell.module("layer_metrics", "sched.active_lanes_mean").read(ctx)
    if lanes is None:
        return None
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
        cell.config, lanes)
    return 100.0 * (nbytes / peaks[kind]["hbm_bytes_per_s"]) / (
        total_s / steps)
