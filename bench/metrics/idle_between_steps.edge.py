"""Share of the traced edge window in which the device ran no program:
the idle time outside every execution on the device's ``XLA Modules``
line (launch latency, argument handling, waiting for the host's next
dispatch). With ``idle_within_steps.edge`` it makes up
``idle_share.edge``."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.trace.steps:
        return None
    between_ns, _ = ctx.trace.idle_split(*ctx.window)
    return 100.0 * between_ns / 1e9 / ctx.window_s
