"""Bytes of index keys one cached token occupies, all layers
(``debug_state()["pool"]["index_bytes_per_token"]``): what a learned indexer
adds to the page store beside K and V.  None on a program or a model without
index rows."""


def read(ctx):
    pool = ctx["counters_after"].get("pool") or {}
    return pool.get("index_bytes_per_token") or None
