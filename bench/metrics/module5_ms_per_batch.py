"""Milliseconds of device op time under the program's ``module5``
scope per edge batch of the traced window: module 5 of the CNN (PERF.md
section 4 says which stages each backbone puts there)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_batch(ctx, "module5")
