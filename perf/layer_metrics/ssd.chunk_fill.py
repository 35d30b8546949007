"""How full the chunks of the Mamba-2 mixer's chunked form were in the window's
rounds: the prompt rows the rounds carried over the rows of the chunks their
programs computed (``debug_state()["ssd"]["round"]``: ``rows`` over ``chunks``
x ``chunk``, after minus before; every count is times the state layers, which
cancels).  A round is as wide as its prompt tokens' power of two and is cut
into whole chunks of 128 rows, so a round of 300 tokens computes four chunks
for 2.3 chunks' rows; 100 % is a round whose tokens fill its width.  None on
a program without the counter."""


def read(ctx):
    a = (ctx["counters_before"].get("ssd") or {}).get("round")
    b = (ctx["counters_after"].get("ssd") or {}).get("round")
    if not a or not b:
        return None
    chunks = b["chunks"] - a["chunks"]
    if not chunks:
        return None
    return 100.0 * (b["rows"] - a["rows"]) / (
        chunks * ctx["counters_after"]["ssd"]["chunk"])
