"""Host time of one decode block's or mixed round's dispatch spent in
the eight ``jnp.asarray`` of the host arrays (for a chained block four of
them are the device-resident carry): what resident block tables would shorten
(``dispatch_parts.put``, seconds over entries in the window).  The three
``sched.dispatch_*_ms`` sum to about ``sched.dispatch_ms``, which also
averages over single ticks and the lines between the parts."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("dispatch_parts", "put", "s"),
                 ("dispatch_parts", "put", "n"), 1e3)
