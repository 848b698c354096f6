"""Share of the traced window in which no operation ran on the device:
1 - busy_s / window_s, in percent (busy is the union of the device's
operation intervals, averaged over the chips)."""


def reduce(ctx):
    if not ctx.busy_s or not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
