"""Keys attended over keys scored, in percent, over the window's query rows
(``debug_state()["sparse"]``: ``keys_attended`` = the sum of ``min(context,
topk)``, ``keys_scored`` = the sum of contexts, decode steps and rounds
together): how sparse the traffic made attention; 100 % while every context
is within ``topk``.  None on a program or a model without the counters."""


def read(ctx):
    a = ctx["counters_before"].get("sparse")
    b = ctx["counters_after"].get("sparse")
    if not a or not b:
        return None
    scored = sum(b["keys_scored"].values()) - sum(a["keys_scored"].values())
    if not scored:
        return None
    return 100.0 * (sum(b["keys_attended"].values())
                    - sum(a["keys_attended"].values())) / scored
