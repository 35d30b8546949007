"""How much of the window's stalls the collector explains: the seconds of
the stalls that a collection of generation 1 or 2 (any thread's: it stops
them all) overlaps, ``host.stalls.gc_s``, over ``host.stalls.s`` (deltas).
Near 100: freeze the heap after warm-up and collect where the scheduler
idles.  Near 0: the thread stood still with no collection inside (the
interpreter lock held elsewhere, the machine's scheduler), and
``host.stalls.by_stage`` says where.  0 where no stall fell in the window;
None on a program that does not count stalls."""

from harness.counters import delta


def read(ctx):
    gc_s, s = delta(ctx, "host", "stalls", "gc_s"), delta(
        ctx, "host", "stalls", "s")
    if gc_s is None or s is None:
        return None
    return 100.0 * gc_s / s if s else 0.0
