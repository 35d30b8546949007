"""Median time per output token over the requests that completed in the
window: per request (last token - first token) / (tokens - 1).  Not the raw
gap: a K-step decode block delivers K tokens at once."""

from harness.sizes import percentile


def read(ctx):
    xs = [(r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1) * 1e3
          for r in ctx["window"]["completed"] if len(r.get("times", ())) > 1]
    return percentile(xs, 50) if xs else None
