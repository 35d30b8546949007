"""Share of the device's busy time in the traced slice that went to mixed
rounds: ``jit_paged_mixed_step`` device seconds over busy seconds (first
chip).  A reading, where PR 24 fitted "~40 %" from dispatch counts."""

PROGRAM = "jit_paged_mixed_step"


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s_per_chip"][0]:
        return None
    rec = trace["modules"].get(PROGRAM)
    if rec is None:
        # none in the slice is a reading of 0 only where the programs
        # carry names at all
        named = any(n.startswith("jit_paged_") for n in trace["modules"])
        return 0.0 if named else None
    return 100.0 * rec["total_s"] / trace["busy_s_per_chip"][0]
