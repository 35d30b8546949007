"""Prompt tokens a mixed round carried, in the mean over the window's rounds:
``mixed_prompt_tokens`` over ``kinds.mixed``.  A round reads every weight once
whatever its rows, so this is what a weight pass, a turn of the host and a gap
in the decode chain bought; the round's budget
(``debug_state()["dispatch"]["round_budget"]``) is its ceiling, reached only
while more prompt is pending than one round takes.  ``mixed_tokens`` counts
the decode rows too; this does not.  None on a program that does not count it,
and in a window without a round."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("mixed_prompt_tokens",), ("kinds", "mixed"))
