"""Share of the device's op time in the traced edge window spent in ops
that carry no program scope: the copies, slices and relayouts XLA adds
around the layers. None where no op carries a scope, as from a program
that names none of its layers."""
from bench import scopes


def read(ctx):
    by_scope = scopes.op_ns_by_scope(ctx.trace, ctx.window)
    total, unscoped = sum(by_scope.values()), by_scope.get(None, 0)
    if total <= 0 or unscoped == total:
        return None
    return 100.0 * unscoped / total
