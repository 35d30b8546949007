"""Output tokens that reached the client inside the window, over its length.
Every stream that did not end in an error counts, also one still running
when the window closed: its tokens arrived.  Host clock."""


def read(ctx):
    win = ctx["window"]
    n = sum(1 for r in win["records"] if not r.get("error")
            for t in r.get("times", ())
            if win["t_start"] <= t <= win["t_end"])
    return n / win["seconds"]
