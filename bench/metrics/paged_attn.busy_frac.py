"""Share of the device's busy time spent in the paged-attention kernel,
both summed over the cell's chips."""
from bench.kernels import is_paged_attn


def read(ctx):
    lo, hi = ctx.trace_window
    busy = ctx.trace.busy_ns(ctx.events, lo, hi)
    k_ns = ctx.trace.op_ns(ctx.events, lo, hi, is_paged_attn)
    return k_ns / busy if busy and k_ns else None
