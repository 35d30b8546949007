"""How far the share of the window's (row, expert) assignments that went to
experts whose weights this chip holds lies from the share of the experts it
holds, in points of that share (``debug_state()["moe"]``: ``assignments_here``
over ``assignments``, after minus before, all expert layers, against ``held``
over the router's columns).  With 128 of 512 experts here an even router
sends this chip 25 %, a deployment's load; one that skews towards the held
range makes this chip's expert products cost more than a deployment's, one
that skews away starves them, and either way the cell stops standing for the
deployment: 0 is the target, so lower is better.  None on a program without
the counter."""


def read(ctx):
    a = ctx["counters_before"].get("moe")
    b = ctx["counters_after"].get("moe")
    if not a or not b or "assignments_here" not in b:
        return None
    here = sum(b["assignments_here"]) - sum(a["assignments_here"])
    total = sum(map(sum, b["assignments"])) - sum(map(sum, a["assignments"]))
    if not total:
        return None
    even = b["held"] / len(b["assignments"][0])
    return 100.0 * abs(here / total - even)
