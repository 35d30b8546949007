"""Largest share of the KV pool's pages in use, sampled every 50 ms by a
thread of the serving process during the window."""


def read(ctx):
    gauges = [g for g in ctx["gauges"] if g.get("n_pages")]
    if not gauges:
        return None
    return 100.0 * max(1.0 - g["free_pages"] / g["n_pages"]
                       for g in gauges)
