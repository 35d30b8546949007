"""Share of the window the process stood still inside CPython's cyclic
collector: the seconds of its collections, all three generations
(``debug_state()["dispatch"]["host"]["gc"]["s"]``, deltas over the window,
timed from the ``start`` to the ``stop`` phase of ``gc.callbacks`` on the
thread that collects), over the window's seconds.  A collection holds the
interpreter lock, so these seconds are lost to EVERY thread, the
scheduler's too, whichever thread crossed the threshold.  None on a
program that does not watch the collector."""

from harness.counters import delta


def read(ctx):
    parts = [delta(ctx, "host", "gc", "s", g)
             for g in ("gen0", "gen1", "gen2")]
    if None in parts or not ctx["window"]["seconds"]:
        return None
    return 100.0 * sum(parts) / ctx["window"]["seconds"]
