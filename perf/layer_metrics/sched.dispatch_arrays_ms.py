"""Host time of one decode block's or mixed round's dispatch spent in
building the numpy block table, lengths, tokens and sampling arrays, a
round's ``pack_round``
(``dispatch_parts.arrays``, seconds over entries in the window).  The three
``sched.dispatch_*_ms`` sum to about ``sched.dispatch_ms``, which also
averages over single ticks and the lines between the parts."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("dispatch_parts", "arrays", "s"),
                 ("dispatch_parts", "arrays", "n"), 1e3)
