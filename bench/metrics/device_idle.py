"""Share of the traced window in which no operation ran on the device:
1 - (union of the device op intervals) / window, averaged over chips."""


def read(ctx):
    w = ctx.window
    if w.busy_s <= 0:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s)
