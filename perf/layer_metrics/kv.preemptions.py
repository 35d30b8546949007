"""Requests the scheduler preempted during the window (its counter)."""


def read(ctx):
    a, b = ctx["counters_before"], ctx["counters_after"]
    if "dispatch" not in b:
        return None
    return b["dispatch"]["preemptions"] - a["dispatch"]["preemptions"]
