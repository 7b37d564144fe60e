"""Milliseconds of device op time under the program's ``moe_experts``
scope per edge batch of the traced window: every MoE layer's routed
grouped matmuls (gate, up, down) and the SwiGLU between them."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.ms_per_batch(ctx, "moe_experts")
