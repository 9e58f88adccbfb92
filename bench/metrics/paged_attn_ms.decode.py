"""Device time of the paged-attention kernel per decode step: the
kernel's operations (``bench.kernels.is_paged_attn``) that lie inside the
traced window's ``engine.megastep`` spans of width ``C`` = 1, over the
number of those spans, in milliseconds. Nothing without the program's
spans or the kernel's time."""
from bench import spans as sp
from bench.kernels import is_paged_attn


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if not spans:
        return None
    lo, hi = ctx.trace_window
    steps = [s for s in sp.in_window(spans, "engine.megastep", lo, hi)
             if s["args"].get("C") == 1]
    if not steps:
        return None
    leaves = ctx.trace.leaves(ctx.events)
    k_ns = sum(sp.ops_inside(leaves, s, is_paged_attn) for s in steps)
    return 1e-6 * k_ns / len(steps) if k_ns else None
