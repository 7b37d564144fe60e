"""Milliseconds of device op time under the program's ``dequantize``
scope per edge batch of the traced window: the dequantize kernel, found
by the scope whichever impl runs it."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_batch(ctx, "dequantize")
