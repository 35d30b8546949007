"""Host time of one decode block's or mixed round's dispatch spent in
the jitted function from entry to return: argument flattening, the C++ fast
path, the enqueue
(``dispatch_parts.call``, seconds over entries in the window).  The three
``sched.dispatch_*_ms`` sum to about ``sched.dispatch_ms``, which also
averages over single ticks and the lines between the parts."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("dispatch_parts", "call", "s"),
                 ("dispatch_parts", "call", "n"), 1e3)
