"""Device time of one mixed round: the median device duration of a
``jit_paged_mixed_step`` execution in the traced slice."""

from harness.sizes import percentile

PROGRAM = "jit_paged_mixed_step"


def read(ctx):
    trace = ctx["trace"]
    if not trace or PROGRAM not in trace["modules"]:
        return None
    return 1e3 * percentile(trace["modules"][PROGRAM]["durations_s"], 50)
