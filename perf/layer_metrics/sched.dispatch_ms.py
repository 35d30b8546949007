"""Host time of one dispatch: building the host arrays, ``jnp.asarray`` and
the call of the jitted program (the scheduler's ``dispatch`` stage, seconds
over entries in the window).  The device idles for as long, wherever no
other block is in flight."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("stages", "dispatch", "s"),
                 ("stages", "dispatch", "n"), 1e3)
