"""Share of the window's plain decode dispatches that were enqueued before
their predecessor was fetched: ``ahead_blocks`` over ``kinds.decode``.  For
such a block the device does not wait for the host's turn (fetch, commit,
plan, the jitted call); the rest are a chain's first block, the blocks
around a completion the host could foresee, and single ticks."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("ahead_blocks",), ("kinds", "decode"), 100.0)
