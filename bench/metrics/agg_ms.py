"""Device milliseconds per epoch of the aggregation: the gather and
scatter ops of the compiled step (and the fusions and index preparation
XLA builds around them), by HLO op kind, summed over the traced window
and divided by the epochs completed in it."""


def read(ctx):
    s = ctx.window.seconds(ctx.ops, lambda op: op.kind == "agg")
    if s <= 0 or ctx.epochs == 0:
        return None
    return 1e3 * s / ctx.epochs
