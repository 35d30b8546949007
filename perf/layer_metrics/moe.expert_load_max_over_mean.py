"""Routing imbalance over the window: the busiest expert's assignments over
the mean expert's, in the worst expert layer (``debug_state()["moe"]
["assignments"]``, after minus before).  1 is a perfectly even load."""


def read(ctx):
    a = ctx["counters_before"].get("moe")
    b = ctx["counters_after"].get("moe")
    if not a or not b:
        return None
    worst = None
    for before, after in zip(a["assignments"], b["assignments"]):
        load = [y - x for x, y in zip(before, after)]
        if sum(load):
            ratio = max(load) * len(load) / sum(load)
            worst = ratio if worst is None else max(worst, ratio)
    return worst
