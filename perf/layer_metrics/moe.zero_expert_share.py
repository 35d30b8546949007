"""Share of the window's (row, column) assignments that went to the router's
identity columns, which cost no expert product (``debug_state()["moe"]``:
``assignments`` from column ``zero_first`` on over all of them, after minus
before, all expert layers).  What a token costs in expert rows is ``top_k``
times one minus this: with 256 identity columns of 768 an even router reads
a third.  None on a program without the counter or a model without such
columns."""


def read(ctx):
    a = ctx["counters_before"].get("moe")
    b = ctx["counters_after"].get("moe")
    if not a or not b or not b.get("zero_columns"):
        return None
    at = b["zero_first"]
    total = sum(map(sum, b["assignments"])) - sum(map(sum, a["assignments"]))
    zero = (sum(sum(row[at:]) for row in b["assignments"])
            - sum(sum(row[at:]) for row in a["assignments"]))
    if not total:
        return None
    return 100.0 * zero / total
