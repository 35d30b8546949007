"""Process start to window start: native core, weights, server, compile or
cache load, the reference check, warm-up.  Host clock."""


def read(ctx):
    return ctx["setup_s"]
