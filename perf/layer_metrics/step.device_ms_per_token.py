"""Device time per generated token: the seconds in which an operation ran
on the device during the traced slice (first chip), over the output tokens
that reached the client in that slice.  The jitted steps carry no names in a
trace yet (the engine jits ``functools.partial`` objects, which XLA calls
``jit__unknown``), so the time is not split by program here."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("host_span"):
        return None
    lo, hi = trace["host_span"]
    tokens = sum(1 for r in ctx["window"]["records"] if not r.get("error")
                 for t in r.get("times", ()) if lo <= t <= hi)
    if tokens == 0:
        return None
    return 1e3 * trace["busy_s_per_chip"][0] / tokens
