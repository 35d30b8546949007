"""The ``emit`` stage's part of a mean turn (``turns.stages.emit`` /
``turns.n``): ``on_token`` callbacks (the RPC's write) and future
resolution for the tokens of the block that ended the chain: what enqueueing
the next block BEFORE the emit would take out of a turn.
With the other three ``sched.turn_*_ms`` it sums to ``sched.turn_ms``."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "stages", "emit"), ("turns", "n"), 1e3)
