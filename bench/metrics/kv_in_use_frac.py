"""Share of the KV pool's bytes in use, averaged over the traced window's
steps (the pool's ``bytes_in_use / bytes_total`` after each step)."""


def read(ctx):
    fr = [s["kv_used"] / s["kv_total"] for s in ctx.steps if s["kv_total"]]
    return sum(fr) / len(fr) if fr else None
