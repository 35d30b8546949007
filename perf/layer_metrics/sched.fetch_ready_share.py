"""How often the host came after the device: of the window's blocking
fetches (``debug_state()["dispatch"]["fetches"]``, deltas), the share whose
array was READY when the fetch began (``is_ready()``, asked once before the
blocking ``np.asarray``).  Such a fetch waits for no device: the program and
its copy to the host had ended, so the device stood idle from there to the
next launch, and the fetch's seconds are the copy and the interpreter lock.
Lower is better: the host is ahead.  None on a program that does not ask."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("fetches", "ready_n"), ("fetches", "n"), 100.0)
