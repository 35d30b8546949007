"""Share of the HBM roofline the compaction of one finished window reaches:
the bytes it must move (``perf/rooflines/evabyte.py`` ``summary_cost``: the
window's rows of every layer read once, one row in ``chunk_size`` written)
over the device's HBM bandwidth (``perf/peaks.json``), over the MEAN device
time of a ``jit_paged_eva_compact`` execution in the traced slice (the
program is the ``eva_chunk_summary`` kernel and the scatter of its rows).
None where the slice holds no compaction, or on a program without one."""

PROGRAM = "jit_paged_eva_compact"


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    if not trace or not ctx["counters_after"].get("eva"):
        return None
    times = trace["modules"].get(PROGRAM, {}).get("durations_s")
    bandwidth = cell.module("layer_metrics",
                            "gdn.decode_roofline").hbm_bytes_per_s()
    if not times or bandwidth is None:
        return None
    nbytes = cell.module("rooflines", cell.config["kind"]).summary_cost(
        cell.config)["bytes"]
    return 100.0 * (nbytes / bandwidth) / (sum(times) / len(times))
