"""Of the keys the window's DECODE rows attended, the share that were
summaries, in percent (``debug_state()["dispatch"]["lane_work"]["decode"]``:
``summary_keys`` over ``keys``, both counted by the scheduler from the
positions it committed: a row at position ``p`` attends ``128 (p // 2048)``
summaries and ``p % 2048 + 1`` rows of its own window).  How much of a
decode step's attention the compacted form carries: 0 while every context
is inside one window.  None on a program or a model without the counter."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("lane_work", "decode", "summary_keys"),
                 ("lane_work", "decode", "keys"), 100.0)
