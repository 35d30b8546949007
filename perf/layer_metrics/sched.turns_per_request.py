"""Turns the scheduler took per completed request: ``turns.n`` over
``completed_requests``.  A request costs a turn where its lane joins the
running chain, one where its step budget ends, and one for every mixed
round its prompt rides; times ``sched.turn_ms`` it is the device's wait per
request."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "n"), ("completed_requests",))
