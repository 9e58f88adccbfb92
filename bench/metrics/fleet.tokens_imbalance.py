"""How unevenly the fleet router spread the work: the real tokens that
the busiest engine served in the traced steps over the engines' mean
(1.0 is even). Nothing in a cell of one engine."""


def read(ctx):
    if ctx.members < 2:
        return None
    real = [0] * ctx.members
    for s in ctx.steps:
        real[s["member"]] += s["real"]
    mean = sum(real) / ctx.members
    return max(real) / mean if mean else None
