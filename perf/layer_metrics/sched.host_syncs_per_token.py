"""Blocking device-to-host fetches per generated token, from the
scheduler's own counters over the window."""


def delta(ctx, *path):
    a, b = ctx["counters_before"], ctx["counters_after"]
    for key in path:
        a, b = a[key], b[key]
    return b - a


def read(ctx):
    if "dispatch" not in ctx["counters_after"]:
        return None
    tokens = delta(ctx, "dispatch", "tokens_generated")
    if tokens <= 0:
        return None
    return delta(ctx, "dispatch", "decode_host_syncs") / tokens
