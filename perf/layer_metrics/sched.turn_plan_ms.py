"""The ``plan`` and ``admit`` stages' part of a mean turn
(``turns.stages.plan`` + ``turns.stages.admit``, over ``turns.n``): the
admission after the commit, the sweep at the top of a pass, ``_plan_decode``
or a round's page securing.  With the other three ``sched.turn_*_ms`` it
sums to ``sched.turn_ms``."""

from harness.counters import delta


def read(ctx):
    parts = [delta(ctx, "turns", "stages", s) for s in ("plan", "admit")]
    n = delta(ctx, "turns", "n")
    if None in parts or not n:
        return None
    return 1e3 * sum(parts) / n
