"""Mean length of a turn that followed a completion (a decode block that
got no successor because a lane's step budget ended inside it):
``turns.by_cause.completion.s`` / ``.n``, deltas over the window.  The turn
whose emit holds a whole block's tokens: what enqueueing the next chain's
first block BEFORE the emit works on.  ``sched.turn_ms`` is the mean over
every kind of turn.  None on a program that does not count turns by cause,
or where no such turn closed in the window."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "by_cause", "completion", "s"),
                 ("turns", "by_cause", "completion", "n"), 1e3)
