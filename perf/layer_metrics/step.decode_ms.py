"""Device time of one decode step: the median, over the executions of the
``jit_paged_decode_block_k<K>`` programs in the traced slice, of an
execution's device duration over its K."""

import re

from harness.sizes import percentile

PROGRAM = re.compile(r"^jit_paged_decode_block_k(\d+)$")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    xs = [1e3 * d / int(m.group(1))
          for name, rec in trace["modules"].items()
          for m in [PROGRAM.match(name)] if m
          for d in rec["durations_s"]]
    return percentile(xs, 50) if xs else None
