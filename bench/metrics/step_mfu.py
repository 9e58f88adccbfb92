"""The whole step's share of the cell's bf16 peak, in percent: model FLOPs
of the real tokens the traced steps served on every chip (the cell's
architecture module's ``model_flops``, padding not counted) over the
traced window's length times the chips times one chip's peak."""


def read(ctx):
    lo, hi = ctx.trace_window
    if hi <= lo or not ctx.steps or ctx.peaks is None:
        return None
    f = sum(ctx.flops.model_flops(ctx.dims, s["rows"]) for s in ctx.steps)
    return 100.0 * f / ((hi - lo) * 1e-9 * ctx.chips
                        * ctx.peaks["bf16_flops"])
