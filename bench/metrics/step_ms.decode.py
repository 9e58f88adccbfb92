"""Mean host-clock time of the engine steps in which no row prefilled
(dispatch width 1), from the call to the sampled ids on the host."""


def read(ctx):
    ts = [s["t1"] - s["t0"] for s in ctx.steps if s["C"] == 1]
    return 1e3 * sum(ts) / len(ts) if ts else None
