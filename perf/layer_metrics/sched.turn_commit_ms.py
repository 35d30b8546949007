"""The ``commit`` stage's part of a mean turn (``turns.stages.commit`` /
``turns.n``): the locked bookkeeping after the fetch that opened the
turn (lengths, tokens, spans, releases).
With the other three ``sched.turn_*_ms`` it sums to ``sched.turn_ms``."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "stages", "commit"), ("turns", "n"), 1e3)
