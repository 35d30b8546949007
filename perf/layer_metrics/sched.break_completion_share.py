"""Of the decode blocks that got no successor before their fetch
(``debug_state()["dispatch"]["chain"]["breaks"]``, deltas over the window),
the share where a lane's step budget ended inside the block, which the host
foresees: the share that enqueueing the next chain's first block before the
emit works on.
None on a program that does not count the causes, or where no chain broke."""

from harness.counters import delta


def read(ctx):
    causes = ctx["counters_after"].get("dispatch", {}).get(
        "chain", {}).get("breaks", ())
    counts = {c: delta(ctx, "chain", "breaks", c) for c in causes}
    total = sum(n for n in counts.values() if n)
    if not total or counts.get("completion") is None:
        return None
    return 100.0 * counts["completion"] / total
