"""A throw-away per-layer metric that exists only for perf/tests: the number
of requests the client started.  It is a new file and edits none."""


def read(ctx):
    return len(ctx["window"]["records"])
