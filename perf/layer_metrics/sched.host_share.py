"""Share of the window the scheduler thread spent working on the host: the
``admit``, ``plan``, ``dispatch``, ``commit`` and ``emit`` stages of its
passes (``debug_state()["dispatch"]["stages"]``, delta over the window) over
the window's seconds.  With ``sched.fetch_share`` and the ``idle`` stage it
accounts for the window."""

from harness.counters import stage_share


def read(ctx):
    return stage_share(ctx, "admit", "plan", "dispatch", "commit", "emit")
