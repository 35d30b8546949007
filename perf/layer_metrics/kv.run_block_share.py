"""Share of the full key blocks the window's decode rows walked whose pages
were one ascending run of ids, which the page walk fetches with ONE copy
where a scattered block costs one a page (``debug_state()["pool"]``:
``walk_run_blocks`` over ``walk_blocks``, after minus before; a block by the
attention kernels' own geometry, a decode row a step of a block or a row of
a round).  What the pool's run allocator keeps once the pool has recycled.
None on a program without the counter, and where no row walked a full
block."""


def read(ctx):
    a = ctx["counters_before"].get("pool") or {}
    b = ctx["counters_after"].get("pool") or {}
    if "walk_blocks" not in a or "walk_blocks" not in b:
        return None
    blocks = b["walk_blocks"] - a["walk_blocks"]
    if not blocks:
        return None
    return 100.0 * (b["walk_run_blocks"] - a["walk_run_blocks"]) / blocks
