"""Share of the traced edge window in which a program was executing on
the device and no op of it ran: stalls between the ops of one batch's
program, at kernel boundaries or waiting on copies. With
``idle_between_steps.edge`` it makes up ``idle_share.edge``."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.trace.steps:
        return None
    _, within_ns = ctx.trace.idle_split(*ctx.window)
    return 100.0 * within_ns / 1e9 / ctx.window_s
