"""Share of the traced edge window in which no op ran on the device."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
