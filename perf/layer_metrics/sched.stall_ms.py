"""Mean length of a stall of the window (``host.stalls.s`` /
``host.stalls.n``, deltas): to read beside the traced tail's longest gap.
(``host.stalls.max_s`` is since the process began and holds the warm-up's
compiles: for a person, not for a reader.)  0 where no stall fell in the
window (every cell's line has to carry the metric); None on a program that
does not count stalls."""

from harness.counters import delta


def read(ctx):
    s, n = delta(ctx, "host", "stalls", "s"), delta(
        ctx, "host", "stalls", "n")
    if s is None or n is None:
        return None
    return 1e3 * s / n if n else 0.0
