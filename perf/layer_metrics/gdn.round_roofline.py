"""Share of the HBM roofline a mixed round of kind ``qwen3_next`` reaches: the
bytes a round must move (``perf/rooflines/qwen3_next.py`` ``round_bytes``:
every held weight once, and for the lanes that had a SEGMENT in the round
(the lane whose prompt advances and the decoding lanes that joined it: the
lanes of a chain in flight skip a round) their state read and written and
the K/V rows at or before their last row) over the device's HBM bandwidth
(``perf/peaks.json``), over the MEAN device time of a
``jit_paged_mixed_step`` execution in the traced slice.  Lanes and context
are the window's, from what the scheduler dispatched (``lane_work["round"]``
over ``kinds["mixed"]``, as ``gdn.decode_roofline`` reads them).  A round of
256 prompt tokens is also ~0.9 TFLOP of expert and projection products (5 ms
at the bf16 peak), so its floor is not the bytes alone: read it beside
``step.mixed_round_ms``."""

PROGRAM = "jit_paged_mixed_step"


def read(ctx):
    trace, cell = ctx["trace"], ctx["cell"]
    state = ctx["counters_after"].get("state") or {}
    if not trace or state.get("kind") != "gdn":
        return None
    times = trace["modules"].get(PROGRAM, {}).get("durations_s")
    decode = cell.module("layer_metrics", "gdn.decode_roofline")
    at = decode.lanes_and_context(ctx, "round", "kinds", "mixed")
    bandwidth = decode.hbm_bytes_per_s()
    if not times or at is None or bandwidth is None:
        return None
    nbytes = cell.module("rooflines", cell.config["kind"]).round_bytes(
        cell.config, at[0], at[1])
    return 100.0 * (nbytes / bandwidth) / (sum(times) / len(times))
