"""The scheduler thread's milliseconds in one compaction of a lane's
finished window, over the window's compactions (``debug_state()["eva"]``:
``compact_s`` over the sum of ``compactions``): the page ids packed and
sent, the program's call, the lane's pages behind its new last row returned
to the pool.  The program is never fetched, so the device's time is not in
it (``eva.summary_roofline`` reads that from the trace).  None on a program
or a model without the counter, or a window without a compaction."""


def read(ctx):
    a = ctx["counters_before"].get("eva")
    b = ctx["counters_after"].get("eva")
    if not a or not b:
        return None
    n = sum(b["compactions"].values()) - sum(a["compactions"].values())
    if n <= 0:
        return None
    return 1e3 * (b["compact_s"] - a["compact_s"]) / n
