"""Share of the traced window in which no operation ran on the device,
as the mean over the cell's chips of each chip's idle share:
1 - (sum over chips of the union of its op intervals) / (chips x window)."""


def read(ctx):
    lo, hi = ctx.trace_window
    if hi <= lo:
        return None
    return 1.0 - ctx.trace.busy_ns(ctx.events, lo, hi) / (ctx.chips
                                                          * (hi - lo))
