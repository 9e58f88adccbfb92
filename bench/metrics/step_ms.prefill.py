"""Mean host-clock time of the engine steps with at least one prefilling
row (dispatch width above 1)."""


def read(ctx):
    ts = [s["t1"] - s["t0"] for s in ctx.steps if s["C"] > 1]
    return 1e3 * sum(ts) / len(ts) if ts else None
