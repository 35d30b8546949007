"""Share of the window's mixed rounds that were enqueued before their
predecessor (a decode block or a round) was fetched: ``ahead_rounds`` over
``kinds.mixed``.  For such a round the device does not wait for the host's
turn (fetch, commit, plan, the jitted call); the rest head a chain: a
prompt's first round behind the completion that freed its lane, the round
behind a compaction, a round whose predecessor held a lane the carry cannot
speak for.  None on a program that does not count it, and in a window
without a round."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("ahead_rounds",), ("kinds", "mixed"), 100.0)
