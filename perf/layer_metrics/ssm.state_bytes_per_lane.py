"""Bytes of recurrent state a lane holds, all Mamba layers, whatever its
context (``debug_state()["state"]["bytes_per_lane"]``): the float32 SSM state
and the convolution's tail.  Guards the second kind of state: one kept
narrower, or a layer that loses its slot, moves it.  None on a program (or a
model) without a lane-state store."""


def read(ctx):
    state = ctx["counters_after"].get("state")
    if not state or "bytes_per_lane" not in state:
        return None
    return state["bytes_per_lane"]
