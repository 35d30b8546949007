"""95th percentile, over the attempted requests that completed, of the first
token's arrival minus the request's DUE time (open loop: a stall is charged
to every request it delays).  Host clock, client side."""

from harness.sizes import percentile


def samples(ctx):
    return [(r["times"][0] - (r["due"] if r.get("due") is not None
                              else r["sent"])) * 1e3
            for r in ctx["window"]["completed"] if r.get("times")]


def read(ctx):
    xs = samples(ctx)
    if not xs:
        return None
    ctx["say"](f"ttft_p95_ms over {len(xs)} requests "
               f"(median {percentile(xs, 50):.1f} ms)")
    return percentile(xs, 95)
