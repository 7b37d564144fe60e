"""Seconds of XLA compilation, persistent-cache loads included, during the
run's set-up, from JAX's monitoring events."""


def read(ctx):
    return ctx.setup["compile_s"]
