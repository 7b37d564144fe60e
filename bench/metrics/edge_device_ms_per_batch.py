"""Milliseconds in which the device ran an op, per edge batch of the
traced window."""


def read(ctx):
    batches = ctx.info["batches"]
    if not batches or not ctx.trace.ops:
        return None
    return ctx.busy_s * 1e3 / batches
