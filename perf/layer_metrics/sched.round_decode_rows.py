"""Decode rows a mixed round carried, in the mean over the window's rounds:
``mixed_decode_rows`` over ``kinds.mixed``.  A round reads every weight once
whatever its rows, so a decoding lane that rides it as a row gets its token
out of a weight pass the prompt paid for; a round that carries none leaves
the decoding lanes to a decode block of their own (a second weight pass).
Since PR 49 a round behind an un-fetched block or round takes these rows
from its predecessor's device carry, so a running chain no longer keeps the
decoding lanes out of it.  None on a program that does not count it, and in
a window without a round."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("mixed_decode_rows",), ("kinds", "mixed"))
