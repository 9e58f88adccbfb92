"""Mean dispatcher time per pass while the device waits: over the traced
window's ``sched.pass`` spans (the passes that step the engine), the pass
minus its ``sched.step`` child (the backend's step, lock included), in
milliseconds. Nothing without the program's spans."""
from bench import spans as sp


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if not spans:
        return None
    lo, hi = ctx.trace_window
    passes = sp.in_window(spans, "sched.pass", lo, hi)
    if not passes:
        return None
    host = [sp.self_ns(spans, p, ("sched.step",)) for p in passes]
    return 1e-6 * sum(host) / len(host)
