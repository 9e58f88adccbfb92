"""95th percentile of the MLFQ queue wait: from ``AgentRM.submit`` to the
dispatcher's ``begin_turn``, for turns submitted in the traced window (a
turn not yet begun counts with its wait so far)."""


def read(ctx):
    h0, h1 = ctx.window
    return ctx.stats.quantile(ctx.stats.queue_wait_samples(ctx.turns, h0, h1),
                              0.95)
