"""Bytes of residual state a token row holds between sublayers: ``hc_mult x
hidden_size`` values in the dtype the program holds its streams in
(``debug_state()["mhc"]["stream_bytes_per_row"]``; 28,672 in bf16 at the
published widths, four times a plain residual's).  Guards the residual path:
streams held wider, or fewer of them, move it.  None on a program (or a
model) without hyper-connections."""


def read(ctx):
    mhc = ctx["counters_after"].get("mhc")
    if not mhc or "stream_bytes_per_row" not in mhc:
        return None
    return mhc["stream_bytes_per_row"]
