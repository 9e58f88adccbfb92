"""95th percentile of time to first token in the traced window, by the
end-to-end definition (``stats.ttft_samples``): every turn due in the
window, from its due time to its first token, a turn with no token yet
counting with its wait so far. Read per layer where about a hundred
turns a window leave too few samples beyond the 95th percentile for a
bound to hold."""


def read(ctx):
    h0, h1 = ctx.window
    return ctx.stats.quantile(ctx.stats.ttft_samples(ctx.turns, h0, h1), 0.95)
