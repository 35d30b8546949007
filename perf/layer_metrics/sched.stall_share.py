"""Share of the window the scheduler thread spent in STALLS: single runs
of a working stage (admit, plan, dispatch, commit, emit; never a wait) of
``ContinuousBatcher.STALL_S`` = 20 ms or more
(``debug_state()["dispatch"]["host"]["stalls"]["s"]``, delta over the
window, over its seconds).  The stages' sums and means cannot hold one
entry of 100 ms among 2,500 of 3 ms; this counts only those.  Measured with
the profiler off, over the whole window: set it against the one long gap of
the traced tail (``breakdown.idle_gaps``).  None on a program that does not
count stalls."""

from harness.counters import delta


def read(ctx):
    s, seconds = delta(ctx, "host", "stalls", "s"), ctx["window"]["seconds"]
    if s is None or not seconds:
        return None
    return 100.0 * s / seconds
