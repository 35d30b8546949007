"""Mean steps of a plain decode dispatch over the window:
``decode_block_steps`` (the sum of K) over ``kinds.decode``."""

from harness.counters import ratio


def read(ctx):
    return ratio(ctx, ("decode_block_steps",), ("kinds", "decode"))
