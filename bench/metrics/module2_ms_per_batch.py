"""Milliseconds of device op time under the program's ``module2``
scope per edge batch of the traced window: module 2 of the CNN (PERF.md
section 4 says which stages each backbone puts there)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_batch(ctx, "module2")
