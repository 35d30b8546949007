"""The ``dispatch`` stage's part of a mean turn (``turns.stages.dispatch`` /
``turns.n``): host arrays, their transfer and the jitted call of
the program that ends the turn, up to the call's return
(``sched.dispatch_{arrays,put,call}_ms`` split a dispatch).
With the other three ``sched.turn_*_ms`` it sums to ``sched.turn_ms``."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("turns", "stages", "dispatch"), ("turns", "n"), 1e3)
