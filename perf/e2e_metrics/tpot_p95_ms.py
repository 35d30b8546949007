"""Time per output token: per request (last token - first token) / (tokens
- 1), then the 95th percentile over requests.  Not the raw gap between
tokens: a K-step decode block delivers K tokens at once, so raw gaps are 0
or K steps.  Host clock, client side."""

from harness.sizes import percentile


def samples(ctx):
    return [(r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1) * 1e3
            for r in ctx["window"]["completed"]
            if len(r.get("times", ())) > 1]


def read(ctx):
    xs = samples(ctx)
    if not xs:
        return None
    ctx["say"](f"tpot_p95_ms over {len(xs)} requests "
               f"(median {percentile(xs, 50):.2f} ms)")
    return percentile(xs, 95)
