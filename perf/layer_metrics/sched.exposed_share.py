"""Share of the window the scheduler thread worked while NO dispatched
program was un-fetched, so the device had nothing queued: the seconds of
its turns (``debug_state()["dispatch"]["turns"]["s"]``, delta over the
window) over the window's seconds.  A turn opens where a fetch returns with
nothing behind it and closes where the next step call returns.  These are
the host seconds that cost tokens; ``sched.host_share`` also counts the work
done under a block the device is computing.  It should read a little under
the device's idle share of the same interval (a fetch returns a transfer
after the device ended, a call returns before the device starts).  None on
a program that does not count turns."""

from harness.counters import delta


def read(ctx):
    s, seconds = delta(ctx, "turns", "s"), ctx["window"]["seconds"]
    if s is None or not seconds:
        return None
    return 100.0 * s / seconds
