"""Mean number of lanes that hold a request, sampled every 50 ms by a thread
of the serving process during the window (the scheduler's own
``active_lanes``): the batch a decode dispatch can carry."""


def read(ctx):
    xs = [g["active_lanes"] for g in ctx["gauges"] if "active_lanes" in g]
    return sum(xs) / len(xs) if xs else None
