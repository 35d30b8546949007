"""Experts with at least one row in a decode step, the mean over the window's
decode steps and the expert layers (``debug_state()["moe"]``: ``experts_hit``
over ``decode_steps`` x expert layers).  What a decode step has to read of an
expert layer's routed weights."""


def read(ctx):
    a = ctx["counters_before"].get("moe")
    b = ctx["counters_after"].get("moe")
    if not a or not b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if not steps:
        return None
    return (b["experts_hit"] - a["experts_hit"]) / (
        steps * len(b["expert_layers"]))

