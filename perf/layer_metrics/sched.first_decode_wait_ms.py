"""Mean engine-side wait from a request's first token to its second, over
the requests whose second token was committed in the window: what a newly
admitted lane waits for the running dispatched-ahead chain."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("first_decode_wait_s",), ("first_decode_waits",), 1e3)
