"""Share of the HBM roofline a decode step of kind ``evabyte`` reaches: the
bytes a step must move (``perf/rooflines/evabyte.py`` ``decode_step_bytes``:
the layers' weights and the one head that is read, once, and for the lanes
that RAN a step the rows at or before their row: the summaries of their
finished windows and their own window's rows) over the device's HBM
bandwidth (``perf/peaks.json``), over the MEAN device time of a decode step
in the traced slice: the construction of ``gdn.decode_roofline``, whose
helpers it uses.  Lanes and rows are the window's, from what the scheduler
dispatched (``lane_work["decode"]``: ``passes`` over ``decode_block_steps``
lanes a step, ``keys`` over ``passes`` rows a lane).  Weights are counted
once and activations not at all, so the bytes are a lower bound of a step's
traffic.  None on a program or a model without ``debug_state()["eva"]``."""


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    if not trace or not ctx["counters_after"].get("eva"):
        return None
    gdn = cell.module("layer_metrics", "gdn.decode_roofline")
    at = gdn.lanes_and_context(ctx, "decode", "decode_block_steps")
    bandwidth = gdn.hbm_bytes_per_s()
    if at is None or bandwidth is None:
        return None
    total_s = steps = 0
    for name, rec in trace["modules"].items():
        m = gdn.PROGRAM.match(name)
        if m:
            total_s += sum(rec["durations_s"])
            steps += int(m.group(1)) * len(rec["durations_s"])
    if not steps:
        return None
    nbytes = cell.module("rooflines", cell.config["kind"]).decode_step_bytes(
        cell.config, at[0], at[1])
    return 100.0 * (nbytes / bandwidth) / (total_s / steps)
