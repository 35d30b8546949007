"""Host time per generated token in the scheduler's ``emit`` stage: the
``on_token`` callbacks (the RPC's write) and future resolution, outside the
lock but on the scheduler thread."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("stages", "emit", "s"), ("tokens_generated",), 1e6)
