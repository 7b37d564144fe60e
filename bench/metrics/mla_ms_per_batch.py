"""Milliseconds of device op time under the program's ``mla`` scope per
edge batch of the traced window: the latent attention of every edge
layer (projections, the latent's norm, RoPE, causal attention and the
output projection)."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.ms_per_batch(ctx, "mla")
