"""Mean engine-side wait from submit to prefill start (pages secured), over
the requests whose prefill started in the window."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("queue_wait_s",), ("queue_waits",), 1e3)
