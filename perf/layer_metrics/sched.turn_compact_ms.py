"""Mean length of a turn that followed a block held back for a compaction
(a lane at the end of its EVA window: its rows are compacted before another
is written): ``turns.by_cause.compact.s`` / ``.n``, deltas over the window.
What moving the compaction behind the round works on.  None on a program
that does not count turns by cause, or where no such turn closed in the
window (every cell but the one with EVA windows)."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "by_cause", "compact", "s"),
                 ("turns", "by_cause", "compact", "n"), 1e3)
