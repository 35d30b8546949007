"""Bytes of the page store one cached token occupies, all layers: the pool's
bytes over its pages times the page size (``debug_state()["pool"]``).  Guards
the cache-entry kind: a latent row a token a layer, not K and V of every
head."""


def read(ctx):
    pool = ctx["counters_after"].get("pool")
    if not pool or not pool.get("n_pages") or "hbm_bytes" not in pool:
        return None
    return pool["hbm_bytes"] / (pool["n_pages"] * pool["page_size"])
