"""Mean length of a turn: the scheduler's seconds with nothing un-fetched
on the device's queue over the turns taken (``turns.s`` / ``turns.n``).  One
turn is what a chain break, a mixed round or a single tick costs the
device in waiting.  Its four stages are ``sched.turn_commit_ms``,
``sched.turn_emit_ms``, ``sched.turn_plan_ms`` and
``sched.turn_dispatch_ms``, which sum to it."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "s"), ("turns", "n"), 1e3)
