"""Of the decode blocks that got no successor before their fetch
(``debug_state()["dispatch"]["chain"]["breaks"]``, deltas over the window),
the share where a lane that finished its prompt while the chain ran waited to
join: the share that folding a joining lane into the carry would remove.
None on a program that does not count the causes, or where no chain broke."""

from harness.counters import delta


def read(ctx):
    causes = ctx["counters_after"].get("dispatch", {}).get(
        "chain", {}).get("breaks", ())
    counts = {c: delta(ctx, "chain", "breaks", c) for c in causes}
    total = sum(n for n in counts.values() if n)
    if not total or counts.get("joiner") is None:
        return None
    return 100.0 * counts["joiner"] / total
