"""Median first token - send over the requests that completed in the
window, client side.  In a closed loop whose callers match the lanes this is
the prompt's own processing (prefill, or the mixed rounds that carry it)
plus the wait behind the prompts admitted before it."""

from harness.sizes import percentile


def read(ctx):
    xs = [(r["times"][0] - r["sent"]) * 1e3
          for r in ctx["window"]["completed"] if r.get("times")]
    return percentile(xs, 50) if xs else None
