"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""


def read(ctx):
    lo, hi = ctx.trace_window
    if hi <= lo:
        return None
    return 1.0 - ctx.trace.busy_ns(ctx.events, lo, hi) / (hi - lo)
