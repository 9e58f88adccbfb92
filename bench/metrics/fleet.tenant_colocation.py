"""How well the fleet router keeps each tenant's sessions together: at
the traced window's end, the share of sessions that sit on the engine
holding the most sessions of their tenant (1.0: every tenant on one
engine). A session away from its tenant's engine prefills the tenant's
system prompt again. Nothing in a cell of one engine."""
from collections import Counter


def read(ctx):
    if ctx.members < 2 or not ctx.placement:
        return None
    per = Counter(ctx.placement)                 # (tenant, member) -> n
    best = Counter()
    for (tenant, _), n in per.items():
        best[tenant] = max(best[tenant], n)
    return sum(best.values()) / len(ctx.placement)
