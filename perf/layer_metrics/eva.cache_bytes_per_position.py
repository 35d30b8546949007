"""Page bytes the decoding lanes hold over the positions they have taken in,
sampled every 50 ms during the window (the adapter's gauge:
``decode_pages``, ``decode_positions``; a page's bytes over all layers from
``debug_state()["pool"]["page_nbytes"]``): what a position of context costs
a lane whose finished windows are summaries.  A dense cache reads the
pool's bytes a row (131,072 B for ``evabyte-l8``) whatever the context; a
lane that kept the pages behind a boundary would read that too.  None on a
program whose gauge lacks the readings."""


def read(ctx):
    page = (ctx["counters_after"].get("pool") or {}).get("page_nbytes")
    gauges = [g for g in ctx["gauges"] if g.get("decode_positions")]
    if not page or not gauges:
        return None
    return (page * sum(g["decode_pages"] for g in gauges)
            / sum(g["decode_positions"] for g in gauges))
