"""How late the generator sent: 95th percentile of send time - due time.
A starved generator must not be read as a fast server."""

from harness.sizes import percentile


def read(ctx):
    xs = [(r["sent"] - r["due"]) * 1e3 for r in ctx["window"]["records"]
          if r.get("due") is not None and r.get("sent") is not None]
    return percentile(xs, 95) if xs else None
