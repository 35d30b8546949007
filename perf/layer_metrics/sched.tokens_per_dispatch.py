"""Generated tokens per device dispatch over the window.  Dispatches are
the scheduler's ``decode_dispatches`` (K-blocks, single ticks and, under the
ragged plan, mixed rounds: it counts those there too) plus
``prefill_dispatches`` (each emits a first token)."""


def read(ctx):
    a, b = ctx["counters_before"], ctx["counters_after"]
    if "dispatch" not in b:
        return None
    d = {k: b["dispatch"][k] - a["dispatch"][k]
         for k in ("tokens_generated", "decode_dispatches",
                   "prefill_dispatches")}
    n = d["decode_dispatches"] + d["prefill_dispatches"]
    return d["tokens_generated"] / n if n > 0 else None
