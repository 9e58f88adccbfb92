"""Seconds the entry spent compiling (or loading) the megastep buckets:
the sum of the engine's ``compile_seconds``, one entry per bucket."""


def read(ctx):
    cs = ctx.compile_seconds
    return float(sum(cs.values())) if cs else None
