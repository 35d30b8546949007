"""Share of the window the scheduler thread only waited for the device: its
``fetch`` stage (the blocking ``np.asarray`` of a dispatched result) over
the window's seconds.  Higher is better: the host is out of the way."""

from harness.counters import stage_share


def read(ctx):
    return stage_share(ctx, "fetch")
