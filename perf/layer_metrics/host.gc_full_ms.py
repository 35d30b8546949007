"""Mean length of a FULL collection (generation 2) in the window:
``host.gc.s.gen2`` / ``host.gc.n.gen2``.  A full collection walks every
tracked object the process holds (the step programs' jaxprs and lowerings,
the RPC server, the streams) and is the one pause long enough to be a gap
of its own on the device: read it beside the traced tail's longest gap.
0 where no full collection fell in the window (every cell's line has to
carry the metric; ``host.gc.n.gen2`` says how many there were); None on a
program that does not watch the collector."""

from harness.counters import delta


def read(ctx):
    s, n = delta(ctx, "host", "gc", "s", "gen2"), delta(
        ctx, "host", "gc", "n", "gen2")
    if s is None or n is None:
        return None
    return 1e3 * s / n if n else 0.0
