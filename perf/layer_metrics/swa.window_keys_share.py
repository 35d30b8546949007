"""Keys the window layers' rows attended over what the full layers' rows
did, decode steps and rounds together over the window
(``debug_state()["dispatch"]["lane_work"]``: ``window_keys`` beside ``keys``,
a pass of a lane through the layers counted at its last row: what an
attention layer READS of the lane's pages).  100 % is a window nobody has
passed; at a mean context of ~12 k and a window of 1,024 it reads under a
tenth.  None on a program (or a model) without window layers."""

from harness.counters import delta


def read(ctx):
    got = [delta(ctx, "lane_work", kind, name)
           for name in ("window_keys", "keys")
           for kind in ("decode", "round")]
    if None in got or not got[2] + got[3]:
        return None
    return 100.0 * (got[0] + got[1]) / (got[2] + got[3])
