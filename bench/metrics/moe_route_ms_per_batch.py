"""Milliseconds of device op time under the program's ``moe_route`` scope
per edge batch of the traced window: every MoE layer's router (sigmoid
scores, top-k with the correction bias), the sort of the token-slots by
expert, their unsort and the weighted sum."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.ms_per_batch(ctx, "moe_route")
