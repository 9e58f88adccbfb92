"""The paged-attention kernel's share of its roofline, in percent: the
least time its work could take on one chip (the larger of FLOPs over the
bf16 peak and bytes over the HBM bandwidth, per step, with the work
counted from the live shapes by the architecture module's ``paged_attn``),
summed over every engine's steps, over the kernel's device time in the
trace summed over the chips. Nothing when the trace has no kernel time."""
from bench.kernels import is_paged_attn


def read(ctx):
    lo, hi = ctx.trace_window
    k_ns = ctx.trace.op_ns(ctx.events, lo, hi, is_paged_attn)
    if not k_ns or not ctx.steps or ctx.peaks is None:
        return None
    least = 0.0
    for s in ctx.steps:
        f, b = ctx.flops.paged_attn(ctx.dims, s["rows"])
        least += ctx.flops.least_time(f, b, ctx.peaks)[0]
    return 100.0 * least / (k_ns * 1e-9)
