"""Page bytes the decoding lanes hold in BOTH groups of the page store over
the positions they have taken in, sampled every 50 ms during the window (the
adapter's gauge: ``decode_pages`` the full group's, ``decode_window_pages``
the window group's, ``decode_positions``; a page's bytes a group from
``debug_state()["pool"]["groups"]``): the construction of
``eva.cache_bytes_per_position``.  One table for all eight layers of
``mellum2-l8`` would read 16,384 B whatever the context; a lane whose window
layers keep their window alone reads 4,096 B and its window blocks' 12,288 B
a row over its context.  None on a program whose gauge or pool lacks the
readings."""


def read(ctx):
    groups = (ctx["counters_after"].get("pool") or {}).get("groups")
    gauges = [g for g in ctx["gauges"] if g.get("decode_positions")
              and "decode_window_pages" in g]
    if not groups or not gauges:
        return None
    held = sum(g["decode_pages"] * groups["full"]["page_nbytes"]
               + g["decode_window_pages"] * groups["window"]["page_nbytes"]
               for g in gauges)
    return held / sum(g["decode_positions"] for g in gauges)
