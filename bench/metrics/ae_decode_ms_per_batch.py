"""Milliseconds of device op time under the program's ``ae_decode``
scope per edge batch of the traced window: the autoencoder's decode of
the codes back to the split point's channels. Where XLA fuses it into
the next module's first op, that op's time goes to the module."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_batch(ctx, "ae_decode")
