"""Query rows the mixed rounds' attention calls computed a layer, per token
the rounds carried: ``mixed_attn_rows`` over ``mixed_tokens``.  A lane that
holds a prompt chunk costs the round's width ``M``, a decoding lane one row;
1.0 is an attention that computes only rows that hold a token, and what is
above it is a bucket's padding and lanes that share a bucket.  It counts the
rows the scheduler DISPATCHED (host integers from the round's layout), not
rows the kernel measured: it says how far the plan engages, and the kernel's
skip shows in ``step.mixed_round_ms``.  None on a program that does not count
it (one call at ``lanes x M`` rows a round)."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("mixed_attn_rows",), ("mixed_tokens",))
