"""Mean host time of the engine's steps without the wait on the device:
over the traced window's ``engine.megastep`` spans (admission, packing,
dispatch, readback, retirement), the span minus its ``engine.readback``
child, in milliseconds. Nothing without the program's spans."""
from bench import spans as sp


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if not spans:
        return None
    lo, hi = ctx.trace_window
    steps = sp.in_window(spans, "engine.megastep", lo, hi)
    if not steps:
        return None
    host = [sp.self_ns(spans, s, ("engine.readback",)) for s in steps]
    return 1e-6 * sum(host) / len(host)
