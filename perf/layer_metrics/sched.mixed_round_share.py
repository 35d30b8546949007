"""Share of the window's dispatches that were mixed rounds (a prompt chunk
for the lanes being admitted, one token for every lane that decodes), from
the scheduler's ``kinds`` counters.  Under the ragged plan a re-admission
costs such rounds, and each pads every lane to the largest chunk."""


def read(ctx):
    a, b = ctx["counters_before"], ctx["counters_after"]
    if "dispatch" not in b:
        return None
    kinds = {k: v - a["dispatch"]["kinds"][k]
             for k, v in b["dispatch"]["kinds"].items()}
    n = sum(kinds.values())
    return 100.0 * kinds.get("mixed", 0) / n if n > 0 else None
